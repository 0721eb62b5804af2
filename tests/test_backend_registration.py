"""ops/backend: no silent way off the Pallas path on a TPU."""

import sys

import pytest

import ceph_tpu.ops
from ceph_tpu.ops import backend as backend_mod


@pytest.fixture
def fresh_lazy_load(monkeypatch):
    """Run ``_load_lazy`` again on the REAL registry. The registry is
    process-global and the optional backends register themselves once,
    at import: it is loaded for real before anything is patched, never
    rebound, and must come out of the test as it went in."""
    names = backend_mod.available_backends()
    registered = dict(backend_mod._BACKENDS)
    monkeypatch.setattr(backend_mod, "_lazy_done", False)
    yield
    monkeypatch.undo()
    backend_mod._BACKENDS.clear()
    backend_mod._BACKENDS.update(registered)
    assert backend_mod._lazy_done
    assert backend_mod.available_backends() == names


def test_cpu_platform_registers_no_pallas(fresh_lazy_load):
    assert "pallas" not in backend_mod.available_backends()
    assert backend_mod._lazy_done
    with pytest.raises(KeyError, match="pallas"):
        backend_mod.resolve("pallas")


def test_broken_pallas_on_a_tpu_raises_with_its_cause(
        fresh_lazy_load, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(sys.modules, "ceph_tpu.ops.gf_pallas", None)
    monkeypatch.delattr(ceph_tpu.ops, "gf_pallas", raising=False)
    for lookup in (backend_mod.available_backends,
                   lambda: backend_mod.resolve("auto"),
                   lambda: backend_mod.resolve("pallas")):
        # raised on EVERY lookup: the failure is never remembered as
        # "no pallas here" and routed around
        with pytest.raises(RuntimeError, match="pallas backend must "
                                               "load") as err:
            lookup()
        assert isinstance(err.value.__cause__, ImportError)
    assert not backend_mod._lazy_done
