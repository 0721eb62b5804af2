"""Persistent XLA compilation cache + per-signature compile ledger.

The jit caches are per-process, so every process pays every
per-signature warmup compile again. JAX ships the fix: a persistent
compilation cache that serializes compiled executables to disk, so a
signature compiles once per MACHINE, not once per process. This
module owns:

- ``enable()``: turn the cache on with the entry-size/compile-time
  floors dropped to zero so the small GF kernels qualify (disable
  with ``CEPH_TPU_COMPILE_CACHE=0``). WHERE it lives is decided from
  outside: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself
  keeps the cache there and this module sets no directory; when it is
  not, the fixed ``<checkout>/.jax_compile_cache`` (the path is part
  of the cache key, so a directory that moves never hits).
  Idempotent; called from ``bench.py`` and the OSD device-engine
  init.
- the **signature ledger** (``signatures.json`` inside the cache
  dir): per device-entry-point signature, the first-ever (cold)
  compile wall time and the best warm time seen by a LATER process.
  ``DeviceTelemetry.note_compile`` consults it — a signature already
  in the ledger from a previous process counts as a
  ``compile_cache_hits`` (the XLA disk cache serves it), which is how
  a warm run proves the warmup-kill worked (telemetry snapshot on
  every metric line). It lives next to the cache, wherever that is.

The ledger is advisory (best-effort I/O, never raises into the hot
path); the XLA cache itself is what saves the compile.
"""

from __future__ import annotations

import json
import os
import threading

#: ledger file inside the cache dir
LEDGER_NAME = "signatures.json"

_lock = threading.Lock()
_enabled_dir: str | None = None
#: signatures known from PREVIOUS processes (loaded once at enable):
#: a compile of one of these is a persistent-cache hit
_prior: dict[str, dict] = {}
#: signatures first compiled by THIS process (cold entries to persist)
_current: dict[str, dict] = {}


#: JAX's own variable: set, it places the cache (and the ledger)
#: from outside
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``.jax_compile_cache`` next to the ``ceph_tpu`` package (every
    invocation from this checkout shares one cache)."""
    env = os.environ.get(ENV_DIR)
    if env:
        return env
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(pkg_root, ".jax_compile_cache")


def enable(cache_dir: str | None = None) -> str | None:
    """Enable the persistent compilation cache; returns the cache dir
    (None when disabled via env or when JAX refuses the config).
    Idempotent — a second call with the same/None dir is a no-op.
    ``cache_dir`` is a directory of the caller's own (tests); without
    one the directory is :func:`default_dir`, and where that came
    from ``JAX_COMPILATION_CACHE_DIR`` JAX has read the variable
    itself and no directory is set here."""
    global _enabled_dir
    if os.environ.get("CEPH_TPU_COMPILE_CACHE", "1").lower() in (
            "0", "no", "off", "false"):
        return None
    with _lock:
        if _enabled_dir is not None and cache_dir in (None,
                                                      _enabled_dir):
            return _enabled_dir
        from_env = cache_dir is None and bool(os.environ.get(ENV_DIR))
        cache_dir = cache_dir or default_dir()
        try:
            os.makedirs(cache_dir, exist_ok=True)
            import jax
            if not from_env:
                jax.config.update("jax_compilation_cache_dir",
                                  cache_dir)
            # the GF kernels are small and fast-compiling on CPU CI:
            # drop both persistence floors so they still qualify
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
        except Exception:
            return None
        _enabled_dir = cache_dir
        _prior.clear()
        _prior.update(_load_ledger(cache_dir))
        _current.clear()
        return cache_dir


def enabled_dir() -> str | None:
    return _enabled_dir


def _ledger_path(cache_dir: str) -> str:
    return os.path.join(cache_dir, LEDGER_NAME)


def _load_ledger(cache_dir: str) -> dict:
    try:
        with open(_ledger_path(cache_dir)) as f:
            out = json.load(f)
            return out if isinstance(out, dict) else {}
    except Exception:
        return {}


def _persist_locked() -> None:
    assert _enabled_dir is not None
    merged = dict(_prior)
    for sig, ent in _current.items():
        merged[sig] = ent
    try:
        tmp = _ledger_path(_enabled_dir) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(merged, f, indent=1, sort_keys=True)
        os.replace(tmp, _ledger_path(_enabled_dir))
    except Exception:
        pass                       # read-only checkout: ledger skipped


def note_compile(signature: str, seconds: float) -> bool:
    """Record one compilation; returns True when the signature was
    already in the ledger from a PREVIOUS process — i.e. the persistent
    cache could serve it and ``seconds`` is a warm time. In-process
    recompiles of a signature first seen by this process stay cold
    (they are the recompile bug-class, not cache hits)."""
    if _enabled_dir is None:
        return False
    with _lock:
        if _enabled_dir is None:
            return False
        prior = _prior.get(signature)
        if prior is not None:
            # warm: the disk cache had this signature before we started
            ent = dict(prior)
            warm = ent.get("warm_s")
            ent["warm_s"] = round(min(seconds, warm)
                                  if warm is not None else seconds, 4)
            ent["hits"] = int(ent.get("hits", 0)) + 1
            _prior[signature] = ent
            _persist_locked()
            return True
        ent = _current.get(signature)
        if ent is None:
            _current[signature] = {"cold_s": round(seconds, 4)}
            _persist_locked()
        else:
            # same-process recompile: keep the first cold time
            ent["recompiles"] = int(ent.get("recompiles", 0)) + 1
        return False


def ledger() -> dict:
    """Merged {signature: {cold_s, warm_s?, hits?}} view."""
    with _lock:
        merged = {s: dict(v) for s, v in _prior.items()}
        for s, v in _current.items():
            merged[s] = dict(v)
        return merged


def _reset_for_tests() -> None:
    """Drop the enabled state so a test can re-enable from a fresh dir
    (simulates a new process against the same on-disk cache)."""
    global _enabled_dir
    with _lock:
        _enabled_dir = None
        _prior.clear()
        _current.clear()
