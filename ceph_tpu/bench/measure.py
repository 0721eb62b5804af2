"""Shared device-resident measurement machinery.

Wall-timing one launch measures the dispatch and the fetch, not the
kernel, and a chip shared with other users adds contention on top, so
it is wrong in both directions. Both bench harnesses (bench.py, ec_bench --device-resident) measure the
same way: run the kernel inside a jitted ``fori_loop`` with a real
data dependency between iterations, take the slope between two
iteration counts (dispatch/fetch overhead cancels), collect many
slopes across contention windows, and discard any implying more HBM
traffic than the chip can move (a contended SHORT run inflates the
slope to physically impossible numbers — observed TB/s).
"""

from __future__ import annotations

import functools
import json
import os
import time

def min_physical_slope(min_traffic_bytes: int) -> float:
    """The least seconds one iteration moving ``min_traffic_bytes``
    of HBM traffic can take on this device (the noise guard's floor),
    from the one peaks table in ops/cost_model. 0.0 on the CPU
    platform, where there is no device ceiling to hold a slope to."""
    from ceph_tpu.ops import cost_model
    device_peaks = cost_model.peaks()
    if device_peaks is None:
        return 0.0
    return min_traffic_bytes / (device_peaks[0] * 1e9)

#: per-metric last-good GB/s, persisted across rounds so a future run
#: can tell a kernel regression apart from a fully-contended window
#: (the contended-plateau guard in stable_best_slope)
LAST_GOOD_PATH = os.path.join(os.path.dirname(__file__),
                              "last_good.json")


def load_last_good() -> dict:
    try:
        with open(LAST_GOOD_PATH) as f:
            return json.load(f)
    except Exception:
        return {}


def save_last_good(updates: dict) -> None:
    """Merge per-metric GB/s into the persisted last-good file.

    Callers only record CLEAN (non-contended) plateaus, and the merge
    RATCHETS UP: contention only ever lowers a clean-looking plateau,
    so the best value seen is the physical expectation — tracking a
    mildly-contended run downward would erode the guard. Best-effort:
    a read-only checkout must not fail the bench.
    """
    try:
        cur = load_last_good()
        for k, v in updates.items():
            cur[k] = max(v, cur.get(k, 0.0))
        tmp = LAST_GOOD_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cur, f, indent=1, sort_keys=True)
        os.replace(tmp, LAST_GOOD_PATH)
    except Exception:
        pass


def hbm_probe_gbps(nbytes: int = 64 << 20, budget: float = 25.0
                   ) -> float:
    """Independent chip-health probe: plain-XLA elementwise pass over
    ``nbytes`` (reads + writes it → 2x traffic/iter), measured with
    the same chained-slope method but a tiny budget. A healthy v5e
    reports hundreds of GB/s; a heavily contended chip reports a
    fraction of that. Being a different program from the bench kernel,
    it separates "chip is busy" from "our kernel broke" in the
    driver record. Modeled on the reference benchmark shipping its own
    validity recipe (ceph_erasure_code_benchmark.cc:343-356).
    """
    import jax.numpy as jnp

    x0 = jnp.zeros((nbytes // 4,), jnp.uint32)

    def step(x):
        return x + jnp.uint32(1)

    slope, _, _, _ = stable_best_slope(
        step, x0, min_traffic_bytes=2 * nbytes, counts=(8, 40),
        time_budget=budget, stable_n=3, sleep=0.2)
    return 2 * nbytes / slope / 1e9


def chained_slope(step_fn, x0, *, min_traffic_bytes: int,
                  counts: tuple[int, int] = (5, 25), rounds: int = 12,
                  sleep: float = 1.0) -> float:
    """Seconds per iteration of ``step_fn`` (device-resident).

    ``step_fn(x) -> x'`` must carry a data dependency through its
    return value. ``min_traffic_bytes``: the least HBM traffic one
    iteration can possibly move — slopes implying more than
    the device's peak HBM bandwidth for that traffic are rejected as
    noise.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def loop(x, iters):
        def body(i, xx):
            return step_fn(xx)
        return jax.lax.fori_loop(0, iters, body, x)

    def force(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        return int(jnp.sum(leaf.reshape(-1)[::4096]
                           .astype(jnp.uint32)))

    force(loop(x0, 2))                   # warmup / compile
    min_slope = min_physical_slope(min_traffic_bytes)
    slopes = []
    times = {}
    for _ in range(rounds):
        for iters in counts:
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                force(loop(x0, iters))
                best = min(best, time.perf_counter() - t0)
            times[iters] = best
        s = (times[counts[1]] - times[counts[0]]) / (
            counts[1] - counts[0])
        if s >= min_slope:
            slopes.append(s)
        time.sleep(sleep)                # spread contention windows
    if not slopes:                       # all noise-dominated: honest
        slopes = [times[counts[1]] / counts[1]]
    return min(slopes)


def stable_best_slope(step_fn, x0, *, min_traffic_bytes: int,
                      counts: tuple[int, int] = (5, 25),
                      time_budget: float = 240.0, stable_n: int = 5,
                      stable_tol: float = 0.10, sleep: float = 0.5,
                      expect_slope: float | None = None,
                      contended_factor: float = 3.0,
                      extended_budget: float = 480.0,
                      deadline: float | None = None,
                      label: str | None = None,
                      ) -> tuple[float, float, int, bool]:
    """Adaptive best-slope estimator for a SHARED chip.

    A shared chip is contended by other users in bursts, so a fixed
    round count reports whatever the contention happened to be. This keeps
    sampling chained slopes until ``stable_n`` samples agree with the
    best within ``stable_tol`` (the uncontended plateau — contention
    only ever makes slopes WORSE, so the guarded best is the physical
    number) or the time budget runs out.

    ``expect_slope`` closes the round-4 failure mode: under a
    PERSISTENTLY contended window the best slope IS the contended
    slope, the low plateau self-confirms, and the old estimator
    reported a 250x collapse with a tight spread and no flag. When the last-good
    slope for this metric is known (persisted by the caller), a
    plateau more than ``contended_factor`` slower than it is treated
    as contention evidence, not signal: sampling extends by up to
    ``extended_budget`` extra seconds with longer inter-round gaps
    (hunting for a contention gap). If the extended budget also runs
    out contended, the plateau is returned with ``contended=True`` so
    the record is self-describing — never a silent collapse.

    ``deadline`` (round-6, the r5 rc=124 fix): an absolute
    ``time.perf_counter()`` value past which sampling stops no matter
    what — the bench harness hands every metric the same global
    deadline so the WHOLE run is wall-clock-bounded even when
    compiles or contention eat one metric's share (a later metric
    then samples fewer rounds instead of the process being killed
    with every result lost).

    Returns (best_slope_seconds, spread_pct, n_samples, contended):
    ``label`` (round-9 warmup-kill accounting): names this metric's
    warmup compile in device telemetry as ``bench[label]``. With the
    persistent compilation cache enabled the signature lands in the
    cross-process ledger, so a LATER bench invocation's warmup counts
    a compile_cache_hit and records its (much smaller) warm wall time
    next to the cold one — the proof the per-metric cold compiles
    are paid once per machine, not once per round.

    spread_pct is the relative spread of the plateau samples around
    their median — the run-to-run reproducibility figure BASELINE.md
    documents.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def loop(x, iters):
        def body(i, xx):
            return step_fn(xx)
        return jax.lax.fori_loop(0, iters, body, x)

    def force(out):
        leaf = jax.tree_util.tree_leaves(out)[0]
        return int(jnp.sum(leaf.reshape(-1)[::4096]
                           .astype(jnp.uint32)))

    t_warm = time.perf_counter()
    force(loop(x0, 2))                   # warmup / compile
    if label is not None:
        try:
            from ceph_tpu.utils.device_telemetry import telemetry
            telemetry().note_compile(f"bench[{label}]",
                                     time.perf_counter() - t_warm)
        except Exception:
            pass                         # accounting never costs data
    min_slope = min_physical_slope(min_traffic_bytes)
    t_start = time.perf_counter()
    hard_deadline = t_start + time_budget + (
        extended_budget if expect_slope is not None else 0.0)
    if deadline is not None:
        hard_deadline = min(hard_deadline, deadline)
        time_budget = min(time_budget,
                          max(deadline - t_start, 0.0))
    cur_sleep = sleep
    slopes: list[float] = []
    times: dict[int, float] = {}
    first = True

    def looks_contended(best: float) -> bool:
        return (expect_slope is not None
                and best > expect_slope * contended_factor)

    def clean_plateau() -> bool:
        # a CLEAN result needs both: best within the expectation band
        # AND >= stable_n agreeing samples — a single fast outlier
        # past the base budget must not end the extension (it would
        # return spread 0.0 over one sample and, worse, ratchet the
        # last-good expectation onto noise)
        if not slopes:
            return False
        best = min(slopes)
        if looks_contended(best):
            return False
        plateau = [x for x in slopes if x <= best * (1 + stable_tol)]
        return len(plateau) >= stable_n

    # always run at least one sampling round: the no-slopes fallback
    # below reads ``times``, and a zero/elapsed time budget must
    # return the honest fallback, not NameError (r2 advisor low)
    while first or time.perf_counter() - t_start < time_budget or \
            (expect_slope is not None and not clean_plateau()
             and time.perf_counter() < hard_deadline):
        first = False
        times = {}
        for iters in counts:
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                force(loop(x0, iters))
                best = min(best, time.perf_counter() - t0)
            times[iters] = best
        s = (times[counts[1]] - times[counts[0]]) / (
            counts[1] - counts[0])
        if s >= min_slope:               # physically possible only
            slopes.append(s)
            best = min(slopes)
            plateau = [x for x in slopes
                       if x <= best * (1 + stable_tol)]
            if len(plateau) >= stable_n and \
                    time.perf_counter() - t_start > 20.0:
                if not looks_contended(best):
                    break
                # a tight plateau that is >contended_factor slower
                # than the last-good slope: the whole window is
                # contended and the low plateau is self-confirming
                # (the r4 2.12 GB/s failure). Hunt for a contention
                # gap with longer inter-round sleeps instead of
                # accepting it.
                cur_sleep = min(max(cur_sleep * 1.5, 2.0), 8.0)
        time.sleep(cur_sleep)
    if not slopes:
        return times[counts[1]] / counts[1], 100.0, 0, True
    best = min(slopes)
    plateau = sorted(x for x in slopes if x <= best * (1 + stable_tol))
    med = plateau[len(plateau) // 2]
    spread = 100.0 * (max(plateau) - min(plateau)) / med
    return best, round(spread, 1), len(slopes), looks_contended(best)
