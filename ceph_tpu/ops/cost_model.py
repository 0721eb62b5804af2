"""Compiled cost analysis + roofline estimates for device programs.

The bench gate reports MEASURED GB/s; this module adds the number to
judge it against: XLA's compiled cost analysis (FLOPs and bytes
accessed per execution of the exact compiled program) and the chip's
peak FLOP/s + HBM bandwidth give the roofline estimate — the best
GB/s this program could reach if it were perfectly scheduled. A bench
line running far under its roofline is leaving device performance on
the table (kernel/layout work pays); a line AT its roofline can only
get faster by moving less data (algorithm work pays). RapidRAID's
pipelining argument (PAPERS.md) only holds where the host, not the
device, bottlenecks — the roofline check is how a signature proves
which side it is on.

The cost analysis itself degrades to ``None``/``{}`` (it is an XLA
introspection whose key set varies by backend, and a bench line must
never die for a missing estimate). The peaks do not degrade: they come
from ONE table keyed by the device's ``device_kind``, a device that is
not in it is an error, and a CPU run has no roofline share of a device
to give, so on the CPU platform there are no peaks at all.
"""

from __future__ import annotations

#: device_kind -> (HBM GB/s, peak bf16 TFLOP/s) of one chip.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture
#: table): 16 GB HBM2e at 819 GB/s, 197 TFLOP/s bf16 per chip.
PEAKS = {
    "TPU v5 lite": (819.0, 197.0),
}


def peaks() -> tuple[float, float] | None:
    """(peak_GBps, peak_TFLOPs) of the device JAX runs on, or None on
    the CPU platform. An accelerator whose ``device_kind`` is not in
    :data:`PEAKS` raises: a guessed peak would make every roofline
    share wrong without a word."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    try:
        return PEAKS[dev.device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {dev.device_kind!r}; "
            "add it to ceph_tpu.ops.cost_model.PEAKS with its source"
        ) from None


def _extract(ca: dict | None) -> dict | None:
    """The 'flops' / 'bytes accessed' entries of a compiled
    program's cost_analysis() dict (utilization keys ignored)."""
    if ca is None:
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    out = {}
    if flops is not None and flops == flops:   # NaN guard
        out["flops"] = float(flops)
    if nbytes is not None and nbytes == nbytes:
        out["bytes_accessed"] = float(nbytes)
    return out or None


def analyze(fn, *args, signature: str | None = None) -> dict | None:
    """Lower+compile ``fn`` on the concrete ``args`` and return
    ``{"flops", "bytes_accessed"}`` (whichever the backend reports),
    or None. ``fn`` may be jitted or plain (plain is wrapped). With
    ``signature`` the outcome is recorded in the device-telemetry
    per-signature cost table (``device perf dump`` / dashboard).

    This COMPILES the program (the AOT path does not share the jit
    call cache), so call it off the hot path — bench warmups, cache
    misses behind ``CEPH_TPU_COST_ANALYSIS``, tests.
    """
    try:
        import jax
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        compiled = jitted.lower(*args).compile()
        cost = _extract(compiled.cost_analysis())
    except Exception:
        return None
    if cost and signature:
        try:
            from ceph_tpu.utils.device_telemetry import telemetry
            telemetry().note_cost(signature, cost)
        except Exception:
            pass
    return cost


def roofline_gbps(flops: float | None, bytes_accessed: float | None,
                  traffic_bytes: float) -> float | None:
    """Best-case GB/s for a program serving ``traffic_bytes`` of
    logical traffic per execution: execution time is bounded below by
    max(bytes/peak_bw, flops/peak_flops). None on the CPU platform."""
    device_peaks = peaks()
    if device_peaks is None:
        return None
    bw_gbps, tflops = device_peaks
    t = 0.0
    if bytes_accessed:
        t = max(t, bytes_accessed / (bw_gbps * 1e9))
    if flops:
        t = max(t, flops / (tflops * 1e12))
    if t <= 0:
        return None
    return traffic_bytes / t / 1e9


def bench_fields(fn, args, traffic_bytes: float,
                 signature: str | None = None) -> dict:
    """The bench-line payload: ``{"cost_flops", "cost_bytes",
    "roofline_GBps"}`` for the compiled program, or ``{}`` when the
    backend cannot say (a metric line must never lose fields to a
    cost-analysis fault)."""
    cost = analyze(fn, *args, signature=signature)
    if not cost:
        return {}
    out = {}
    if "flops" in cost:
        out["cost_flops"] = round(cost["flops"])
    if "bytes_accessed" in cost:
        out["cost_bytes"] = round(cost["bytes_accessed"])
    rl = roofline_gbps(cost.get("flops"), cost.get("bytes_accessed"),
                       traffic_bytes)
    if rl is not None:
        out["roofline_GBps"] = round(rl, 2)
    return out
