"""Kernel backend dispatch for matrix codecs.

The reference picks its hot kernel at plugin granularity (jerasure vs isa vs
shec all end in different native libraries). Here every matrix codec shares
one kernel contract —

    encode:  parity[m, N] = mat[m, k] (x) data[k, N]   over GF(2^8)
    decode:  wanted[w, N] = dmat[w, p] (x) present[p, N]

— and the backend decides *where* it runs:

- ``numpy``:  the gf256 reference path (always available, bit-exact oracle);
- ``native``: C++ host library via ctypes (ISA-L-style nibble-table SIMD);
- ``jax``:    bit-sliced binary matmul on the TPU MXU (ops/gf_jax.py);
- ``pallas``: fused unpack->MXU->pack kernel (ops/gf_pallas.py; TPU only,
  several times faster than the plain-XLA path).

``auto`` prefers pallas, then jax, then native, then numpy. On a TPU
the Pallas backend is not optional: a ``gf_pallas`` that fails to
import there raises from the first backend lookup, with the cause,
instead of leaving ``auto`` on the plain-XLA path without a word.
All paths are bit-identical (enforced by tests/test_gf_jax.py and
tests/test_native.py — the corpus gate of
src/test/erasure-code/ceph_erasure_code_non_regression.cc applied across
backends instead of across versions).
"""

from __future__ import annotations

import os
from typing import Callable

import numpy as np

from ceph_tpu.ops import gf256

# name -> matvec(mat[m,k] uint8, data[k,N] uint8) -> [m,N] uint8
_BACKENDS: dict[str, Callable[[np.ndarray, np.ndarray], np.ndarray]] = {}
_AUTO_ORDER = ["pallas", "jax", "native", "numpy"]


def register_backend(name: str, fn) -> None:
    _BACKENDS[name] = fn


def available_backends() -> list[str]:
    _load_lazy()
    return [n for n in _AUTO_ORDER if n in _BACKENDS]


register_backend("numpy", gf256.gf_matvec_chunks)

_lazy_done = False


def _load_lazy() -> None:
    """Import optional backends on first use (jax import is expensive)."""
    global _lazy_done
    if _lazy_done:
        return
    import jax
    from ceph_tpu.ops import gf_jax  # noqa: F401  (self-registers)
    if jax.default_backend() == "tpu":
        try:
            from ceph_tpu.ops import gf_pallas
        except Exception as exc:
            raise RuntimeError(
                "on a TPU the pallas backend must load; refusing to "
                f"serve from the plain-XLA path instead: {exc!r}"
            ) from exc
        register_backend("pallas", gf_pallas.matvec)
    # registers "native" when the library built; native_loader logs
    # the cause once when it did not
    from ceph_tpu.ops import native  # noqa: F401
    _lazy_done = True


def resolve(name: str = "auto"):
    """Return (backend_name, matvec_fn)."""
    _load_lazy()
    if name == "auto":
        forced = os.environ.get("CEPH_TPU_BACKEND")
        if not forced:
            # env beats config beats the auto ladder (the layered
            # precedence the rest of g_conf follows)
            from ceph_tpu.utils.config import g_conf
            conf_backend = g_conf()["erasure_code_backend"]
            if conf_backend != "auto":
                forced = conf_backend
        if forced:
            name = forced
        else:
            for cand in _AUTO_ORDER:
                if cand in _BACKENDS:
                    return cand, _BACKENDS[cand]
    if name not in _BACKENDS:
        raise KeyError(
            f"backend {name!r} not available (have {sorted(_BACKENDS)})")
    return name, _BACKENDS[name]


def matvec(mat: np.ndarray, data: np.ndarray, backend: str = "auto") -> np.ndarray:
    _, fn = resolve(backend)
    return fn(mat, data)
