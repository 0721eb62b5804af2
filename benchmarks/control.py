#!/usr/bin/env python3
"""The control: the comparison has to FAIL a system that breaks one
guarantee its configuration states.

    python3 benchmarks/control.py --workload <cell> --seed <n>

The system runs no model and states no precision, so the control is the
plain reference put in the program's place with one stated guarantee
broken, the step that would tempt a later PR, at the cell's own object
size, k, m and sample:

- a write cell, ``one_parity_short``: the last parity shard is not
  computed (it repeats the one before), so the pool survives one
  failure fewer than ``m`` promises; and ``crc_not_kept``: the crc in
  ``hinfo`` is not that of the shard (the seed is stored instead);
- a degraded cell, ``not_reconstructed``: a read with a data shard lost
  returns the surviving chunks with zeros where the lost one was, an
  approximate answer where it was exact;
- a recovery cell, ``rebuilt_by_xor``: the shard rebuilt onto the spare
  is the XOR of the k shards read (a single-parity rebuild, one pass
  and no matrix) and not the code's reconstruct, with the crc the
  push carries over from ``hinfo``: the pool reports clean and holds
  a wrong shard, so it survives one failure fewer than it says.

Each control's observations go through ``compare.compare_objects`` /
``compare.judge``, the same functions a run uses, and every control has
to come out as not correct. Prints each number beside its limit and one
JSON line per control; exits 0 when every control failed the
comparison, 1 when one passed it. Needs no chip and no cluster; the
benchmark's own runs do not run it (tests/benchmarks does, at a small
size).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np              # noqa: E402

import compare                  # noqa: E402
import reference                # noqa: E402
import spec                     # noqa: E402
from loadgen import OpRecord, Payloads, seed_words  # noqa: E402


def _clean_window(n_ops: int, decodes: bool) -> tuple[dict, dict]:
    """A window in which every op was acknowledged and the route
    counters stayed clean: the control breaks the data, nothing
    else."""
    summary = {"attempted": n_ops, "failed": 0, "window_s": 1.0,
               "rebuilt_shards": n_ops}
    engine = {"flushes": 0 if decodes else n_ops,
              "decode_flushes": n_ops if decodes else 0}
    return summary, {"engine": engine, "compiles": 0,
                     "decode_fallbacks": 0}


def observe_control(control: str, names: list[str], payloads: Payloads,
                    pool: dict, ref=reference,
                    rebuilt: dict | None = None) -> list[dict]:
    """What ``Served.observe`` would return from a system that breaks
    the guarantee ``control`` names. ``rebuilt``: object -> the
    position recovery rebuilt."""
    k, m = pool["k"], pool["m"]
    out = []
    for name in names:
        data = payloads.of(name)
        shards = ref.shards(data, pool)
        crcs = reference.shard_crcs(shards)
        if control == "one_parity_short":
            shards[k + m - 1] = shards[k + m - 2].copy() if m > 1 \
                else np.zeros_like(shards[k])
            crcs = reference.shard_crcs(shards)
        elif control == "crc_not_kept":
            crcs = [reference.HINFO_SEED] * (k + m)
        elif control == "rebuilt_by_xor":
            lost = rebuilt[name]
            read = [s for pos, s in enumerate(shards) if pos != lost]
            shards[lost] = np.bitwise_xor.reduce(read[:k], axis=0)
        else:
            raise ValueError(f"no control {control!r}")
        out.append({"name": name, "read_back": data,
                    "shards": {i: s.tobytes()
                               for i, s in enumerate(shards)},
                    "crcs": dict(enumerate(crcs))})
    return out


def read_control(names: list[str], payloads: Payloads, pool: dict,
                 lost: list[int]) -> list[OpRecord]:
    """Reads answered without reconstructing: zeros where the chunks
    of the ``lost`` data shards were."""
    k, unit = pool["k"], pool["stripe_unit"]
    ops = []
    for name in names:
        data = payloads.of(name)
        buf = np.frombuffer(data, dtype=np.uint8).copy()
        stripes = buf[:len(buf) // (k * unit) * (k * unit)].reshape(
            -1, k, unit)
        stripes[:, lost, :] = 0
        rec = OpRecord(name, 0.0)
        rec.ok = True
        rec.equal = buf.tobytes() == data
        ops.append(rec)
    return ops


def run_controls(cell: spec.Cell, seed: int) -> list[dict]:
    mix, pool, ref = cell.traffic, cell.config["pool"], cell.reference
    window = cell.window(None, mix, seed)
    payloads = Payloads(seed, mix["object_bytes"], mix["payload_pool"])
    rng = np.random.default_rng(seed_words(seed) + [9])
    results = []
    if mix["op"] == "recover":
        names = sorted({f"obj_{int(i)}" for i in rng.integers(
            mix["preload_objects"], size=mix["check_sample"])})
        # one victim: an object's PG rebuilt one position, any of k+m
        lost = {name: int(rng.integers(pool["k"] + pool["m"]))
                for name in names}
        observed = observe_control("rebuilt_by_xor", names, payloads,
                                   pool, ref, rebuilt=lost)
        objects = compare.compare_objects(observed, payloads.of, pool,
                                          ref=ref)
        summary, grown = _clean_window(len(names), True)
        summary["rebuilt_positions"] = {n: [p] for n, p in lost.items()}
        cmp = compare.judge(window, summary, [], observed, objects,
                            grown, (1, 0))
        results.append(("rebuilt_by_xor", cmp))
    elif window.degraded:
        names = [f"obj_{int(i)}" for i in rng.integers(
            mix["preload_objects"], size=mix["check_sample"])]
        # one data position: what a PG is left with once the spare
        # OSD has taken over the other (``Served.kill_osds``)
        lost = [int(rng.integers(pool["k"]))]
        ops = read_control(names, payloads, pool, lost)
        clean = compare.compare_objects([], payloads.of, pool, ref=ref)
        summary, grown = _clean_window(len(ops), True)
        cmp = compare.judge(window, summary, ops, [], clean, grown,
                            (1, 0))
        results.append(("not_reconstructed", cmp))
    else:
        names = [f"w{int(t)}_{int(i)}" for t, i in zip(
            rng.integers(mix["clients"], size=mix["check_sample"]),
            rng.integers(1000, size=mix["check_sample"]))]
        for control in ("one_parity_short", "crc_not_kept"):
            observed = observe_control(control, names, payloads, pool,
                                       ref)
            objects = compare.compare_objects(observed, payloads.of,
                                              pool, ref=ref)
            summary, grown = _clean_window(len(names), False)
            cmp = compare.judge(window, summary, [], observed, objects,
                                grown, (1, 0))
            results.append((control, cmp))
    out = []
    for control, cmp in results:
        print(f"control {control}:", file=sys.stderr)
        cmp.print_last()
        out.append({"control": control, "workload": cell.name,
                    "seed": seed, "correct": cmp.correct,
                    "compared": cmp.as_dict()})
    return out


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    results = run_controls(spec.Cell(args.workload, root), args.seed)
    for res in results:
        print(json.dumps(res))
    return 1 if any(res["correct"] for res in results) else 0


if __name__ == "__main__":
    sys.exit(main())
