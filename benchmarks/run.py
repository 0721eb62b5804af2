#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A new process per run: brings the cell's configuration up (mon + OSDs +
clients in this process, one EC pool, every OSD on the one device
engine), warms every flush bucket the cell's traffic can meet, runs the
window the traffic's ``op`` names (``windows/<op>.py``: a closed loop
for ``--seconds``, or a recovery from the down mark until clean),
compares what the window itself wrote, read or rebuilt with the
configuration's plain reference, and prints ONE JSON object as the
last line of standard output:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "compared"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (a few seconds inside the window
are traced with ``jax.profiler``; ``device`` then has ``busy_s`` and
``window_s``). Every number that decided ``correct`` stands beside its
limit under ``compared`` and in the last lines of standard error.

Without a TPU (or with fewer chips than the cell asks for) the command
exits non-zero and prints no result. Everything a cell is made of is a
data file found by name (``spec.py``); nothing is listed in code.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()          # set-up is counted from here

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import traceback                # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare                  # noqa: E402
import spec                     # noqa: E402
import trace_reduce             # noqa: E402
from loadgen import quantile, seed_words  # noqa: E402

#: the traced sub-window of a ``--trace 1`` run: it starts this long
#: after the window and lasts this long (less in a short window)
TRACE_LEAD_S = 2.0
TRACE_SECONDS = 5.0
#: where a run leaves its trace; listed in .gitignore
OUT_DIR = ".bench_out"


class Out:
    """The command's own standard output. fd 1 is duplicated for the
    result line and then pointed at stderr, so whatever else writes to
    standard output in this process (daemon threads, the runtime's C
    code at teardown) lands on stderr and never after the last line.
    Copied from ``chip_smoke.py``."""

    def __init__(self) -> None:
        sys.stdout.flush()
        self._real = os.dup(1)
        os.dup2(2, 1)
        self._py_stdout = sys.stdout
        sys.stdout = sys.stderr
        self._file = os.fdopen(os.dup(self._real), "w")

    def line(self, obj: dict) -> None:
        self._file.write(json.dumps(obj) + "\n")
        self._file.flush()

    def close(self) -> None:
        self._file.close()

    def restore(self) -> None:
        """Put fd 1 and ``sys.stdout`` back (in-process callers)."""
        self.close()
        os.dup2(self._real, 1)
        os.close(self._real)
        sys.stdout = self._py_stdout


def note(**info) -> None:
    """A progress line, on standard error."""
    print(json.dumps({"t": round(time.monotonic() - _T0, 2), **info}),
          file=sys.stderr, flush=True)


def accelerator(chips: int) -> dict:
    """The device as JAX reports it; raises unless JAX has a TPU with
    at least ``chips`` chips."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"JAX found no accelerator (platform {dev.platform!r})")
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX reports "
                           f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax
    peak = 0
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Tracer:
    """Traces ``TRACE_SECONDS`` of the window with ``jax.profiler``
    (host Python tracer off) and keeps the engine's counters at both
    ends, so that the work flushed in the traced window is known."""

    def __init__(self, served, logdir: str, seconds: float) -> None:
        self.served = served
        self.logdir = logdir
        self.lead = min(TRACE_LEAD_S, seconds * 0.2)
        self.length = max(0.5, min(TRACE_SECONDS,
                                   seconds - self.lead - 1.0))
        self.window_s = 0.0
        self.engine: dict = {}

    def __call__(self, t_start: float) -> None:
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        time.sleep(max(0.0, t_start + self.lead - time.monotonic()))
        before = self.served.engine_stats()
        t_a = time.monotonic()
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        try:
            time.sleep(self.length)
        finally:
            after = self.served.engine_stats()
            t_b = time.monotonic()
            jax.profiler.stop_trace()
        self.window_s = t_b - t_a
        self.engine = {k: after[k] - before.get(k, 0) for k in after}

    def reduce(self) -> dict:
        profile = trace_reduce.load(
            trace_reduce.find_xplane(self.logdir))
        for line in trace_reduce.describe(profile):
            print("trace: " + line[:160], file=sys.stderr)
        return trace_reduce.reduce(profile, self.window_s)


def latency_profile(sorted_ms: list[float]) -> dict:
    """For the reader of standard error: where the tail sits."""
    if not sorted_ms:
        return {}
    out = {f"p{int(q * 100)}": round(quantile(sorted_ms, q), 1)
           for q in (0.5, 0.75, 0.9, 0.95, 0.99)}
    out["max"] = round(sorted_ms[-1], 1)
    out["mean"] = round(sum(sorted_ms) / len(sorted_ms), 1)
    out["over_2s"] = sum(1 for v in sorted_ms if v > 2000.0)
    return out


def layer_values(cell: spec.Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something
    to read."""
    values = {}
    for metric in cell.per_layer:
        met = spec.layer_metric(metric["name"], cell.bench_dir)
        read = spec.reader(met["reader"], cell.bench_dir)
        val = read(ctx, **met.get("args", {}))
        if val is not None:
            values[metric["name"]] = float(val)
    return values


def result_line(cell: spec.Cell, trace: int, compared, summary: dict,
                values: dict, device: dict, breakdown: dict | None
                ) -> dict:
    """The contract's object. ``compared`` comes last."""
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {m["name"]: {"value": values[m["name"]],
                           "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    line = {"correct": bool(compared.correct),
            "attempted": int(summary["attempted"]),
            "failed": int(summary["failed"]),
            "metrics": metrics,
            "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared.as_dict()
    return line


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: int,
             device: dict) -> tuple[dict, "compare.Compared"]:
    """One run of one cell on a machine that has the device; returns
    the result line and the numbers compared."""
    from served import Served
    mix, config = cell.traffic, cell.config
    served = Served(config, mix, seed)
    window = cell.window(served, mix, seed)
    note(cell=cell.name, seed=seed, seconds=seconds, trace=trace,
         device=device)
    try:
        served.start()
        served.make_payloads()
        note(phase="up")
        served.warm_writes()
        note(phase="warm_writes", compiles=served.compiles(),
             compile_s=served.compile_seconds())
        if mix["preload_objects"]:
            served.preload()
            note(phase="preload", objects=len(served.preloaded))
        # what the window kind needs from set-up
        window.prepare(note)
        tracer = None
        if trace:
            tracer = Tracer(served, os.path.join(
                cell.root, OUT_DIR, "trace_" + cell.name), seconds)
        before = served.snapshot()
        setup_s = time.monotonic() - _T0
        note(phase="window", setup_s=round(setup_s, 2))
        summary, ops = window.run(seconds, during=tracer)
        grown = served.growth(before, served.snapshot())
        peak = memory_peak_bytes()
        note(phase="window_done", attempted=summary["attempted"],
             failed=summary["failed"], MBps=round(summary["MBps"], 2),
             window_s=round(summary["window_s"], 2),
             latency_ms=latency_profile(summary["latencies_ms"]),
             engine=grown["engine"], compiles=grown["compiles"],
             compiled=grown["compiled"], errors=summary["errors"],
             **{key: val for key, val in summary.items()
                if key not in ("attempted", "failed", "MBps",
                               "window_s", "latencies_ms", "errors")
                and isinstance(val, (int, float, list))})
        primaries = served.primaries_without_device()
        sample = compare.sample_names(window.check_names,
                                      mix["check_sample"],
                                      seed_words(seed))
        observed = served.observe(sample, read_back=window.read_back)
        note(phase="observed", objects=len(observed))
    finally:
        # the program's state is freed before the reference runs
        served.stop()
    objects = compare.compare_objects(
        observed, served.payloads.of, config["pool"],
        absent_ok=window.absent_ok, ref=cell.reference)
    compared = compare.judge(window, summary, ops, observed, objects,
                             grown, primaries)
    if objects["examples"]:
        note(unequal=objects["examples"])
    device = dict(device, memory_peak_bytes=peak)
    breakdown = None
    if trace:
        reduced = tracer.reduce()
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        ctx = {"stages": grown["stages"],
               "engine_window": grown["engine"],
               "engine_traced": tracer.engine, "trace": reduced,
               "peaks": spec.peaks(device["kind"], cell.bench_dir),
               "config": config, "reference": cell.reference,
               "traffic": mix, "loop": summary}
        values = layer_values(cell, ctx)
        note(phase="traced", busy_s=reduced["busy_s"],
             window_s=reduced["window_s"], events=reduced["events"],
             engine_traced=tracer.engine)
    else:
        values = dict(window.values(summary), setup_s=setup_s)
    return result_line(cell, trace, compared, summary, values, device,
                       breakdown), compared


def main(argv=None, root: str = ROOT, device: dict | None = None,
         out: Out | None = None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    own_out = out is None
    if own_out:
        out = Out()             # before JAX is imported
    try:
        try:
            cell = spec.Cell(args.workload, root)
            if device is None:
                device = accelerator(cell.chips)
        except Exception as exc:
            # no such cell, or no accelerator: no result
            print(f"benchmarks/run.py: {exc}", file=sys.stderr)
            return 2
        try:
            line, compared = run_cell(cell, args.seed, args.seconds,
                                      args.trace, device)
        except Exception:
            traceback.print_exc(file=sys.stderr)
            return 1
        # the cluster is stopped and joined; nothing of ours prints to
        # standard output after the line below
        compared.print_last()
        out.line(line)
        return 0
    finally:
        if own_out:
            out.close()


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: the daemons' threads and the runtime's
    # exit hooks have nothing left to say that belongs on stdout
    os._exit(code)
