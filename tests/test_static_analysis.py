"""Static-analysis gate (ISSUE 11, lint half) + the wire round-trip
contract test.

Gate: the four AST lint families over the whole ``ceph_tpu`` package
must report zero findings outside the justified baseline
(``analysis/baseline.json``) and zero stale baseline entries — the
same verdict ``tools/analyze.py`` / ``python -m ceph_tpu.analysis``
exit non-zero on.

Each checker family is additionally proven LIVE by seeding a synthetic
violation (asymmetric message field, traced-value branch, unregistered
counter key, unlocked mutation, ...) and asserting it is caught — so a
refactor that silently lobotomizes a checker fails here, not in some
future incident.

The auto-generated encode→decode round-trip over EVERY message type in
parallel/messages.py (satellite) keeps the wire-symmetry lint and the
runtime contract from drifting apart.
"""

import json
import os
import subprocess
import sys

import pytest

from ceph_tpu.analysis import linters
from ceph_tpu.parallel import messages as M


def _src(text: str, rel: str = "ceph_tpu/synthetic.py"
         ) -> linters.SourceFile:
    return linters.SourceFile("/synthetic/" + rel, text, rel=rel)


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def test_package_gate_zero_new_zero_stale():
    findings = linters.run_all()
    new, stale = linters.diff_baseline(findings)
    assert not new, "NEW lint findings (fix them or justify in " \
        "analysis/baseline.json):\n" + \
        "\n".join(f.format() for f in new)
    assert not stale, "STALE baseline entries (the violation no " \
        f"longer exists; prune them): {[e['key'] for e in stale]}"


def test_lint_baseline_entries_are_justified():
    baseline = linters.load_baseline()
    assert baseline.get("lint"), "baseline should carry the known set"
    for ent in baseline["lint"]:
        assert ent.get("justification", "").strip(), ent
        assert not ent["justification"].startswith("TODO"), \
            f"unjustified baseline entry: {ent['key']}"


def test_cli_entry_points_exit_zero_on_clean_tree():
    for cmd in ([sys.executable, "-m", "ceph_tpu.analysis"],
                [sys.executable, "tools/analyze.py"]):
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              cwd=linters.REPO_ROOT, timeout=300)
        assert proc.returncode == 0, (cmd, proc.stdout, proc.stderr)
        assert "0 new" in proc.stdout


def test_cli_exits_nonzero_on_new_finding(tmp_path):
    bad = tmp_path / "pkg" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(
        "class C:\n"
        "    def __init__(self):\n"
        "        import threading\n"
        "        self._lock = threading.Lock()\n"
        "        self.x = 0\n"
        "    def locked_read(self):\n"
        "        with self._lock:\n"
        "            return self.x\n"
        "    def racy_write(self):\n"
        "        self.x = 1\n")
    from ceph_tpu.tools.analyze import main
    assert main(["--root", str(tmp_path / "pkg")]) == 1


def test_cli_exits_nonzero_on_stale_baseline(tmp_path):
    clean = tmp_path / "pkg" / "ok.py"
    clean.parent.mkdir()
    clean.write_text("X = 1\n")
    stale = tmp_path / "baseline.json"
    stale.write_text(json.dumps({
        "lint": [{"key": "registry_drift:counter-unused:ghost",
                  "justification": "was real once"}],
        "witness": []}))
    from ceph_tpu.tools.analyze import main
    assert main(["--root", str(tmp_path / "pkg"),
                 "--baseline", str(stale)]) == 1


# ---------------------------------------------------------------------------
# family 1: wire symmetry — seeded violations
# ---------------------------------------------------------------------------

def _wire_keys(text: str) -> set[str]:
    fs = linters.check_wire_symmetry(_src(text))
    return {f.key.split(":", 2)[-1] for f in fs}


def test_wire_symmetry_field_order_asymmetry_caught():
    text = '''
class MBad:
    MSG_TYPE = 250
    FIELDS = [("tid", "u64"), ("oid", "str")]
    def encode_payload(self):
        e = Encoder()
        Encoder.u64(e, self.tid)
        Encoder.str(e, self.oid)
        return e.getvalue()
    @classmethod
    def decode_payload(cls, buf):
        d = Decoder(buf)
        msg = cls()
        if not d.eof():
            msg.oid = Decoder.str(d)
        if not d.eof():
            msg.tid = Decoder.u64(d)
        return msg
'''
    keys = _wire_keys(text)
    assert any(k.startswith("MBad:field-order-asymmetry")
               for k in keys), keys


def test_wire_symmetry_one_sided_override_caught():
    text = '''
class MHalf:
    MSG_TYPE = 251
    FIELDS = [("tid", "u64")]
    def encode_payload(self):
        e = Encoder()
        Encoder.u64(e, self.tid)
        return e.getvalue()
'''
    assert "MHalf:override-asymmetry" in _wire_keys(text)


def test_wire_symmetry_unknown_kind_and_dup_caught():
    text = '''
class MA:
    MSG_TYPE = 252
    FIELDS = [("a", "u64"), ("a", "u64"), ("b", "quux")]
class MB:
    MSG_TYPE = 252
    FIELDS = [("c", "u64")]
'''
    keys = _wire_keys(text)
    assert "MA:dup-field:a" in keys
    assert "MA:unknown-kind:b" in keys
    assert "MB:dup-msg-type:252" in keys


def test_wire_symmetry_tail_intolerant_decode_caught():
    text = '''
class MTail:
    MSG_TYPE = 253
    FIELDS = [("tid", "u64"), ("stages", "str")]
    def encode_payload(self):
        e = Encoder()
        Encoder.u64(e, self.tid)
        Encoder.str(e, self.stages)
        return e.getvalue()
    @classmethod
    def decode_payload(cls, buf):
        d = Decoder(buf)
        msg = cls()
        msg.tid = Decoder.u64(d)
        msg.stages = Decoder.str(d)
        return msg
'''
    assert "MTail:decode-not-tail-tolerant" in _wire_keys(text)


def test_wire_symmetry_real_messages_clean():
    src = [s for s in linters.iter_sources()
           if s.rel.endswith("parallel/messages.py")][0]
    assert linters.check_wire_symmetry(src) == []


# ---------------------------------------------------------------------------
# family 2: jit hygiene — seeded violations
# ---------------------------------------------------------------------------

def _jit_keys(body: str) -> set[str]:
    fs = linters.check_jit_hygiene(
        _src(body, rel="ceph_tpu/ops/synthetic.py"))
    return {f.key.split(":", 2)[-1] for f in fs}


def test_jit_traced_branch_caught():
    keys = _jit_keys('''
import jax
@jax.jit
def f(x):
    if x.sum() > 0:
        return x
    return -x
''')
    assert any(k.startswith("f:traced-branch") for k in keys), keys


def test_jit_shape_branch_is_static_and_clean():
    keys = _jit_keys('''
import jax
@jax.jit
def f(x):
    if x.ndim == 1:
        return x
    k, n = x.shape
    if len(x) > 4 and k > 2:
        return x
    return x
''')
    assert not keys, keys


def test_jit_static_argnames_respected():
    keys = _jit_keys('''
import functools, jax
@functools.partial(jax.jit, static_argnames=("rows",))
def f(x, rows):
    if rows > 4:
        return x
    return x
''')
    assert not keys, keys


def test_jit_coercions_caught():
    keys = _jit_keys('''
import jax
@jax.jit
def f(x):
    a = int(x[0])
    b = x.max().item()
    c = np.asarray(x)
    return a + b
''')
    assert any(k.startswith("f:traced-coercion:int") for k in keys)
    assert any(k.startswith("f:traced-coercion:item") for k in keys)
    assert any(k.startswith("f:host-pull") for k in keys)


def test_shard_map_wrapped_callee_walked():
    """ISSUE 12: a function handed to shard_map is traced exactly
    like a decorated jit body — the hygiene rules walk it."""
    keys = _jit_keys('''
import jax
from jax.experimental.shard_map import shard_map
def build(mesh):
    def step(x):
        if x.sum() > 0:
            return x
        return -x
    return jax.jit(shard_map(step, mesh=mesh,
                             in_specs=None, out_specs=None))
''')
    assert any(k.startswith("step:traced-branch") for k in keys), keys


def test_in_shardings_wrapped_callee_walked():
    """...and so is the first arg of a jit call carrying
    in_shardings/out_shardings (the pjit seam), and the global_fn/
    shard_fn kwargs of mesh_compile.compile_step."""
    keys = _jit_keys('''
import jax
def build(mesh):
    def gstep(x):
        return x + int(x[0])
    return jax.jit(gstep, in_shardings=None, out_shardings=None)

def build2(mesh, mesh_compile, specs):
    def body(x):
        return np.asarray(x)
    return mesh_compile.compile_step(
        mesh, global_fn=body, shard_fn=body,
        in_specs=specs, out_specs=specs)
''')
    assert any(k.startswith("gstep:traced-coercion:int")
               for k in keys), keys
    assert any(k.startswith("body:host-pull") for k in keys), keys


def test_plain_jit_call_without_shardings_not_walked():
    """A bare ``jax.jit(fn)`` call (no shardings) keeps its historical
    treatment: only decorator sites and wrapper seams are walked, so
    the rule adds no blanket findings to the existing call-style
    entry points."""
    keys = _jit_keys('''
import jax
def build():
    def fn(x):
        return x + int(x[0])
    return jax.jit(fn)
''')
    assert not keys, keys


def test_jit_closure_device_array_caught():
    keys = _jit_keys('''
import jax, jax.numpy as jnp
def build(table):
    idx = jnp.asarray(table)
    @jax.jit
    def step(x):
        return x[idx]
    return step
''')
    assert "step:closure-device-array:idx" in keys, keys


# ---------------------------------------------------------------------------
# family 3: registry drift — seeded violations
# ---------------------------------------------------------------------------

def _drift_keys(*texts: str) -> set[str]:
    drift = linters.RegistryDrift()
    for i, t in enumerate(texts):
        drift.collect(_src(t, rel=f"ceph_tpu/synthetic{i}.py"))
    return {f.key for f in drift.findings()}


def test_drift_unregistered_counter_caught():
    keys = _drift_keys(
        "perf.add_u64_counter('good')\n"
        "perf.inc('good')\n"
        "perf.inc('ghost_key')\n")
    assert "registry_drift:counter-unregistered:ghost_key" in keys
    assert "registry_drift:counter-unused:good" not in keys


def test_drift_unused_counter_caught_and_fstring_family_not():
    keys = _drift_keys(
        "perf.add_u64_counter('never_touched')\n"
        "perf.add_u64_counter('faults_x')\n"
        "perf.add_u64_counter('faults_y')\n"
        "perf.inc(f'faults_{kind}')\n")
    assert "registry_drift:counter-unused:never_touched" in keys
    assert "registry_drift:counter-unused:faults_x" not in keys


def test_drift_unknown_option_caught():
    keys = _drift_keys(
        "from ceph_tpu.utils.config import g_conf\n"
        "x = g_conf()['no_such_option']\n")
    assert "registry_drift:unknown-option:no_such_option" in keys


def test_drift_unread_option_caught():
    keys = _drift_keys(
        "Option('dead_knob', int, 1)\n")
    assert "registry_drift:option-unread:dead_knob" in keys


def test_drift_asok_unregistered_invoke_caught():
    keys = _drift_keys(
        "asok.register_command('real cmd', handler)\n"
        "asok_command(path, 'real cmd')\n"
        "asok_command(path, 'phantom cmd')\n")
    assert "registry_drift:asok-unregistered:phantom cmd" in keys
    assert "registry_drift:asok-unregistered:real cmd" not in keys


def test_drift_tuner_knob_unobserved_caught():
    """ISSUE 13: a tuner-managed knob (the live utils/knobs registry
    names them) whose Option is declared with NO observer consumer
    anywhere is flagged — runtime pushes would either pay a hot-path
    config read or never land."""
    bad = _drift_keys(
        "Option('engine_window', int, 3)\n"
        "x = g_conf()['engine_window']\n")
    assert "registry_drift:tuner-knob-unobserved:engine_window" \
        in bad
    # a direct add_observer consumer clears it
    good = _drift_keys(
        "Option('engine_window', int, 3)\n"
        "x = g_conf()['engine_window']\n"
        "g_conf().add_observer('engine_window', fn)\n")
    assert not any("tuner-knob-unobserved:engine_window" in k
                   for k in good)
    # ...as does the engine's _observe_knob seam
    seam = _drift_keys(
        "Option('mesh_flush_bytes', int, 1)\n"
        "x = g_conf()['mesh_flush_bytes']\n"
        "self._observe_knob('mesh_flush_bytes', fn)\n")
    assert not any("tuner-knob-unobserved:mesh_flush_bytes" in k
                   for k in seam)
    # ...as does the tracer's _CFG_KEYS loop-over-keys idiom
    keys_idiom = _drift_keys(
        "Option('trace_sample_every', int, 64)\n"
        "x = g_conf()['trace_sample_every']\n"
        "_CFG_KEYS = ('trace_sample_every',)\n")
    assert not any(
        "tuner-knob-unobserved:trace_sample_every" in k
        for k in keys_idiom)
    # a non-tuner option never triggers this finding
    other = _drift_keys(
        "Option('mon_lease', float, 5.0)\n"
        "x = g_conf()['mon_lease']\n")
    assert not any("tuner-knob-unobserved" in k for k in other)


# ---------------------------------------------------------------------------
# family 4: lock discipline — seeded violations
# ---------------------------------------------------------------------------

def _lock_keys(text: str) -> set[str]:
    fs = linters.check_lock_discipline(_src(text))
    return {f.key.split(":", 1)[-1] for f in fs}


_LOCK_CLASS = '''
import threading
class Daemon:
    def __init__(self):
        self._lock = threading.Lock()
        self._table = {{}}
    def read(self):
        with self._lock:
            return dict(self._table)
    {method}
'''


def test_unlocked_mutation_caught():
    keys = _lock_keys(_LOCK_CLASS.format(method=(
        "def clobber(self):\n"
        "        self._table = {}\n")))
    assert "ceph_tpu/synthetic.py:Daemon.clobber:_table" in keys


def test_locked_mutation_clean():
    keys = _lock_keys(_LOCK_CLASS.format(method=(
        "def safe(self):\n"
        "        with self._lock:\n"
        "            self._table = {}\n")))
    assert not keys, keys


def test_locked_suffix_convention_respected():
    keys = _lock_keys(_LOCK_CLASS.format(method=(
        "def clobber_locked(self):\n"
        "        self._table = {}\n")))
    assert not keys, keys


def test_caller_holds_lock_context_respected():
    keys = _lock_keys(_LOCK_CLASS.format(method=(
        "def _clobber(self):\n"
        "        self._table = {}\n"
        "    def entry(self):\n"
        "        with self._lock:\n"
        "            self._clobber()\n")))
    assert not keys, keys


def test_make_lock_seam_counts_as_a_lock():
    text = '''
from ceph_tpu.analysis.lock_witness import make_lock
class Daemon:
    def __init__(self):
        self._lock = make_lock("daemon.state")
        self._q = []
    def read(self):
        with self._lock:
            return list(self._q)
    def racy(self):
        self._q = []
'''
    assert "ceph_tpu/synthetic.py:Daemon.racy:_q" in _lock_keys(text)


# ---------------------------------------------------------------------------
# notify-under-lock (ISSUE 17)
# ---------------------------------------------------------------------------

def _notify_keys(text: str) -> set[str]:
    return {f.key for f in
            linters.check_notify_under_lock(_src(text))}


_NOTIFY_CLASS = '''
import threading
class Daemon:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv_lock = threading.Lock()
        self._cv = threading.Condition(self._cv_lock)
    {method}
'''


def test_notify_under_foreign_lock_caught():
    keys = _notify_keys(_NOTIFY_CLASS.format(method=(
        "def hurry_up_and_wait(self):\n"
        "        with self._lock:\n"
        "            with self._cv:\n"
        "                self._cv.notify_all()\n")))
    assert "notify_under_lock:ceph_tpu/synthetic.py:" \
        "Daemon.hurry_up_and_wait:_cv" in keys


def test_notify_under_own_lock_clean():
    # Python REQUIRES holding the cond's own lock to notify — the
    # canonical `with self._cv: self._cv.notify()` must not flag,
    # nor holding the exact lock the cond was built over
    keys = _notify_keys(_NOTIFY_CLASS.format(method=(
        "def ok(self):\n"
        "        with self._cv:\n"
        "            self._cv.notify()\n"
        "    def ok2(self):\n"
        "        with self._cv_lock:\n"
        "            self._cv.notify_all()\n")))
    assert not keys, keys


def test_notify_after_release_clean():
    keys = _notify_keys(_NOTIFY_CLASS.format(method=(
        "def polite(self):\n"
        "        with self._lock:\n"
        "            self._ready = True\n"
        "        with self._cv:\n"
        "            self._cv.notify_all()\n")))
    assert not keys, keys


def test_notify_under_lock_sees_make_condition_seam():
    text = '''
from ceph_tpu.analysis.lock_witness import make_condition, make_lock
class Daemon:
    def __init__(self):
        self._lock = make_lock("daemon.state")
        self._cv = make_condition("daemon.cv")
    def racy(self):
        with self._lock:
            self._cv.notify()
'''
    assert "notify_under_lock:ceph_tpu/synthetic.py:" \
        "Daemon.racy:_cv" in _notify_keys(text)


# ---------------------------------------------------------------------------
# satellite: auto-generated wire round-trip over every message type
# ---------------------------------------------------------------------------

def _value_for(kind: str, salt: str):
    return {
        "u8": 7, "u16": 300, "u32": 70_000, "u64": 1 << 40,
        "i32": -5, "i64": -(1 << 40), "f64": 3.5, "bool": True,
        "str": f"s-{salt}", "bytes": b"b-" + salt.encode(),
        "str_map": {"k1": f"v-{salt}", "k2": "v2"},
        "bytes_map": {"k": b"v-" + salt.encode()},
        "i32_list": [-1, 2, 3],
        "u64_list": [1, 99, 1 << 33],
        "str_list": [f"a-{salt}", "b"],
        "bytes_list": [b"x", b"y-" + salt.encode()],
    }[kind]


def _all_message_classes():
    return sorted(M._REGISTRY.items())


@pytest.mark.parametrize(
    "mtype,cls", _all_message_classes(),
    ids=[c.__name__ for _, c in _all_message_classes()])
def test_every_message_roundtrips_field_for_field(mtype, cls):
    """Populate EVERY field (optional/appended ones included) with a
    non-default value; encode -> decode_message -> field-for-field
    equality. This is the runtime twin of the wire-symmetry lint."""
    kwargs = {name: _value_for(kind, name)
              for name, kind in cls.FIELDS}
    msg = cls(**kwargs)
    out = M.decode_message(mtype, msg.encode_payload())
    assert type(out) is cls
    for name, kind in cls.FIELDS:
        assert getattr(out, name) == kwargs[name], \
            f"{cls.__name__}.{name} ({kind}) did not round-trip"


@pytest.mark.parametrize(
    "mtype,cls",
    [(t, c) for t, c in _all_message_classes() if len(c.FIELDS) > 1],
    ids=[c.__name__ for _, c in _all_message_classes()
         if len(c.FIELDS) > 1])
def test_appended_fields_are_tail_tolerant(mtype, cls):
    """An older peer that only knew the first field sends a short
    payload; the decode keeps defaults for every appended field
    (the stages/trace appended-optional contract)."""
    from ceph_tpu.utils.encoding import Encoder
    name0, kind0 = cls.FIELDS[0]
    body = Encoder()
    M._ENC[kind0](body, _value_for(kind0, name0))
    e = Encoder()
    e.section(1, body)
    out = M.decode_message(mtype, e.getvalue())
    assert getattr(out, name0) == _value_for(kind0, name0)
    fresh = cls()
    for name, kind in cls.FIELDS[1:]:
        assert getattr(out, name) == getattr(fresh, name), \
            f"{cls.__name__}.{name}: truncated payload must leave " \
            "the default"


def test_registry_covers_every_declared_class():
    """Every Message subclass in the module with a non-zero MSG_TYPE
    is registered (so the parametrized round-trip above is complete)."""
    import inspect
    declared = [obj for _, obj in inspect.getmembers(M, inspect.isclass)
                if issubclass(obj, M.Message) and obj is not M.Message
                and obj.MSG_TYPE]
    assert {c.MSG_TYPE for c in declared} == set(M._REGISTRY)


# ---------------------------------------------------------------------------
# family 5: fsync seam (ISSUE 14) — seeded violations
# ---------------------------------------------------------------------------

def _fsync_keys(text: str, rel: str = "ceph_tpu/store/synthstore.py"
                ) -> set[str]:
    fs = linters.check_fsync_seam(_src(text, rel=rel))
    return {f.key for f in fs}


def test_untimed_fsync_in_store_caught():
    keys = _fsync_keys('''
import os

class SynthStore:
    def commit(self):
        self._wal.flush()
        os.fsync(self._wal.fileno())
''')
    assert "untimed-fsync:ceph_tpu/store/synthstore.py:commit" in keys


def test_untimed_fdatasync_in_store_caught():
    keys = _fsync_keys('''
import os

def barrier(fd):
    os.fdatasync(fd)
''')
    assert ("untimed-fsync:ceph_tpu/store/synthstore.py:barrier"
            in keys)


def test_fsync_outside_store_dir_not_flagged():
    """The seam contract scopes to ceph_tpu/store/ — the seam's own
    os.fsync (utils/store_telemetry) and unrelated callers are not
    findings."""
    assert _fsync_keys('''
import os

def anywhere(fd):
    os.fsync(fd)
''', rel="ceph_tpu/utils/synth.py") == set()


def test_timed_seam_calls_are_clean():
    """A store that routes through the seam produces zero findings."""
    assert _fsync_keys('''
from ceph_tpu.utils import store_telemetry

class SynthStore:
    def commit(self):
        store_telemetry.timed_fsync(self._wal.fileno(), site="synth")
        store_telemetry.timed_sync("synth.data", self._data.sync)
''') == set()


def test_real_store_files_have_no_untimed_fsyncs():
    """The live contract: every durability barrier in the shipped
    stores goes through the seam TODAY (kv.py's WAL/compact fsyncs,
    the blockstore data-file fdatasync — both engines)."""
    store_srcs = [s for s in linters.iter_sources()
                  if s.rel.replace(os.sep, "/").startswith(
                      "ceph_tpu/store/")]
    assert store_srcs
    for src in store_srcs:
        assert linters.check_fsync_seam(src) == [], src.rel

# ---------------------------------------------------------------------------
# family 6: layering — seeded violations, then the live tree by package
# ---------------------------------------------------------------------------

def _layer_keys(text: str,
                rel: str = "ceph_tpu/store/synth.py") -> set[str]:
    fs = linters.check_layering(_src(text, rel=rel))
    return {f.key for f in fs}


def test_layering_upward_import_caught():
    assert _layer_keys('''
from ceph_tpu.osd.osd import OSD
import ceph_tpu.mgr.tuner as tuner
''') == {"layering:ceph_tpu/store/synth.py:osd",
          "layering:ceph_tpu/store/synth.py:mgr"}


def test_layering_function_local_upward_import_caught():
    """Deferring the import into a function hides the cycle from the
    interpreter, not the dependency from the reader."""
    assert _layer_keys('''
def tail():
    try:
        from ceph_tpu.qa import cluster
    except Exception:
        return None
''') == {"layering:ceph_tpu/store/synth.py:qa"}


def test_layering_same_layer_import_clean():
    assert _layer_keys('''
from ceph_tpu.parallel import messages
from ceph_tpu.store.kv import KV
''') == set()


def test_layering_downward_and_foreign_imports_clean():
    assert _layer_keys('''
import numpy as np
from ceph_tpu.utils import config
from ceph_tpu.ops import gf
from ceph_tpu import models

def f():
    import jax
    from ceph_tpu.analysis.lock_witness import make_lock
''') == set()


def test_layering_benchmark_import_from_program_caught():
    """The benchmark, the old harness, the smoke and the tests read
    the program; the program reads none of them — from any layer."""
    assert _layer_keys('''
from benchmarks import harness
import bench

def probe():
    import chip_smoke
    from tests.conftest import anything
''', rel="ceph_tpu/tools/synth.py") == {
        "layering:ceph_tpu/tools/synth.py:benchmarks",
        "layering:ceph_tpu/tools/synth.py:bench",
        "layering:ceph_tpu/tools/synth.py:chip_smoke",
        "layering:ceph_tpu/tools/synth.py:tests"}
    # the package of the same name is not the old harness
    assert _layer_keys("from ceph_tpu.bench import load_gen\n",
                       rel="ceph_tpu/tools/synth.py") == set()


def test_layering_relative_and_dynamic_imports_resolved():
    """``from ..osd import x`` and ``__import__("ceph_tpu.osd.x")``
    name a package as surely as the absolute statement does."""
    assert _layer_keys('''
import importlib
from ..osd import ec_util
from . import kv
from .kv import KV

def late():
    a = __import__("ceph_tpu.client.rados", fromlist=["RadosClient"])
    b = importlib.import_module("ceph_tpu.services.rgw")
    return importlib.import_module(a.name)
''') == {"layering:ceph_tpu/store/synth.py:osd",
          "layering:ceph_tpu/store/synth.py:client",
          "layering:ceph_tpu/store/synth.py:services"}


def test_layering_package_missing_from_table_is_a_finding():
    """The map cannot fall behind the tree: a new package must be
    placed before it can be imported or can import."""
    assert _layer_keys("X = 1\n", rel="ceph_tpu/newpkg/mod.py") == {
        "layering:unmapped:newpkg"}
    assert _layer_keys("from ceph_tpu.newpkg import mod\n") == {
        "layering:unmapped:newpkg"}


def test_layering_baseline_arrow_accepted_and_stale_reported():
    findings = linters.check_layering(_src(
        "from ceph_tpu.osd import ec_util\n",
        rel="ceph_tpu/store/synth.py"))
    baseline = {"lint": [
        {"key": "layering:ceph_tpu/store/synth.py:osd",
         "justification": "debt: moves down with ec_util"},
        {"key": "layering:ceph_tpu/store/synth.py:mgr",
         "justification": "debt: paid, nobody pruned it"}]}
    new, stale = linters.diff_baseline(findings, baseline)
    assert new == []
    assert [e["key"] for e in stale] == [
        "layering:ceph_tpu/store/synth.py:mgr"]
    new, _ = linters.diff_baseline(findings, {"lint": []})
    assert [f.key for f in new] == [
        "layering:ceph_tpu/store/synth.py:osd"]


def test_layering_outside_the_package_not_flagged():
    """The benchmark and the tests import the whole program."""
    assert _layer_keys("from ceph_tpu.qa.cluster import MiniCluster\n",
                       rel="benchmarks/synth.py") == set()


_PACKAGES = sorted(
    d for d in os.listdir(linters.PKG_ROOT)
    if os.path.isfile(os.path.join(linters.PKG_ROOT, d, "__init__.py")))


@pytest.mark.parametrize("pkg", _PACKAGES)
def test_layering_live_package_has_no_arrow_outside_baseline(pkg):
    """The live contract, one case a package so a failure names it:
    every import in the package names its own layer or a lower one,
    or is a justified debt in analysis/baseline.json."""
    srcs = linters.iter_sources(os.path.join(linters.PKG_ROOT, pkg))
    assert srcs
    findings = [f for src in srcs
                for f in linters.check_layering(src)]
    new, _stale = linters.diff_baseline(findings)
    assert not new, "\n".join(f.format() for f in new)


def test_layering_table_places_every_live_package_once():
    placed = [p for layer in linters.LAYERS for p in layer]
    assert sorted(placed) == _PACKAGES
    assert len(placed) == len(set(placed))


# ---------------------------------------------------------------------------
# family 7: flow context (ISSUE 20) — seeded violations
# ---------------------------------------------------------------------------

def _flow_keys(text: str,
               rel: str = "ceph_tpu/osd/synth.py") -> set[str]:
    fs = linters.check_flow_context(_src(text, rel=rel))
    return {f.key for f in fs}


def test_flow_context_dropped_at_qos_seam_caught():
    keys = _flow_keys('''
class SynthWQ:
    def enqueue(self, key, fn, qos="client"):
        self._queues[qos].append((key, fn))
''')
    assert ("flow_context:ceph_tpu/osd/synth.py:SynthWQ.enqueue"
            in keys)


def test_flow_context_captured_at_qos_seam_clean():
    assert _flow_keys('''
from ceph_tpu.utils import flow_telemetry as _flows

class SynthWQ:
    def enqueue(self, key, fn, qos="client"):
        fn._flow = _flows.capture_flow(qos)
        self._queues[qos].append((key, fn))
''') == set()


def test_flow_context_current_flow_read_also_satisfies():
    assert _flow_keys('''
from ceph_tpu.utils import flow_telemetry as _flows

def submit(op, qos):
    op.flow = _flows.current_flow() or ""
    _ship(op, qos)
''') == set()


def test_flow_context_seam_module_itself_exempt():
    """flow_telemetry's own helpers take qos by construction — the
    module that DEFINES the seam is not a violation of it."""
    assert _flow_keys('''
def capture_flow(qos="client"):
    return ("", qos)
''', rel="ceph_tpu/utils/flow_telemetry.py") == set()


def test_flow_context_live_tree_clean():
    """The live contract: every shipped qos= seam threads the flow
    context TODAY (ShardedOpWQ.enqueue captures it into the work
    item)."""
    for src in linters.iter_sources():
        assert linters.check_flow_context(src) == [], src.rel
