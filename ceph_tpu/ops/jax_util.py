"""Small shared jax helpers for the kernel modules."""

from __future__ import annotations


def tracing_active() -> bool:
    """True when called under a jax trace (jit/vmap/...), False on the
    eager path. Used by the device-matrix caches: under a trace they
    must hand out fresh numpy constants (a cached jnp array would be a
    leaked tracer); eagerly they reuse a device-resident copy (a numpy
    constant there would re-upload the matrix every call).

    tests/test_gf_jax.py pins the BEHAVIOR — eager vs traced must
    differ — so a jax rename of the probe fails CI instead of silently
    degrading the hot path.
    """
    import jax

    return not jax.core.trace_ctx.is_top_level()
