"""The work functions of the rooflines on known shapes, and the
readers' rule that nothing to read is no reading (never 0)."""

import pytest

from bench_tiny import BENCH_DIR  # noqa: F401  (sets sys.path)

import spec
import work
from loadgen import Payloads, quantile, seed_words

MIB = 1 << 20


def test_padded_object_bytes():
    assert work.padded_object_bytes(4 * MIB, 8, 4096) == 4 * MIB
    assert work.padded_object_bytes(1, 8, 4096) == 8 * 4096
    assert work.padded_object_bytes(8 * 4096 + 1, 8, 4096) == \
        2 * 8 * 4096


@pytest.mark.parametrize("ops,obj,k,m,want", [
    (1, 4 * MIB, 8, 3, 4 * MIB * 11 / 8),
    (10, 4 * MIB, 8, 3, 10 * 4 * MIB * 11 / 8),
    (3, 1 * MIB, 4, 2, 3 * 1 * MIB * 6 / 4),
])
def test_encode_hbm_bytes(ops, obj, k, m, want):
    pool = {"k": k, "m": m, "stripe_unit": 4096, "plugin": "jerasure"}
    assert work.encode_hbm_bytes(ops, obj, pool) == want


RS = {"k": 8, "m": 3, "stripe_unit": 4096}


def test_decode_hbm_bytes_reads_k_survivors_once():
    import reference
    assert work.decode_hbm_bytes(5, 4 * MIB, RS) == 5 * 4 * MIB
    # the RS reference states no cheaper rebuild: k shards it is
    assert work.decode_hbm_bytes(5, 4 * MIB, RS, reference) == \
        5 * 4 * MIB
    assert set(work.WORK) == {"encode_hbm_bytes", "decode_hbm_bytes"}


def test_decode_hbm_bytes_asks_the_configurations_reference():
    """A codec that rebuilds from less than k whole shards states so
    in its reference module, and the roofline reads that work."""
    import shec_reference
    pool = {"plugin": "shec", "technique": "single", "k": 6, "m": 4,
            "c": 3, "stripe_unit": 4096}
    # windows of m=4, c=3 over k=6 are 4, 5, 4 and 5 columns wide
    shard = 6 * MIB // 6
    assert shec_reference.rebuild_read_bytes(pool, 6 * MIB) == \
        4 * shard
    assert work.decode_hbm_bytes(3, 6 * MIB, pool, shec_reference) == \
        3 * 4 * shard
    assert work.decode_hbm_bytes(3, 6 * MIB, pool) == 3 * 6 * MIB
    read = spec.reader("roofline_pct")
    args = {"work": "decode_hbm_bytes", "ops_counter": "decode_ops"}
    ctx = _ctx(trace={"busy_s": 0.01, "window_s": 5.0},
               engine_traced={"decode_ops": 3},
               traffic={"object_bytes": 6 * MIB})
    ctx["config"] = {"pool": pool}
    by_k = read(ctx, **args)
    by_ref = read(dict(ctx, reference=shec_reference), **args)
    assert by_ref == pytest.approx(by_k * 4 / 6)


def _ctx(**over):
    ctx = {"stages": {}, "engine_window": {}, "engine_traced": {},
           "trace": None, "peaks": spec.peaks("TPU v5 lite"),
           "config": {"pool": {"k": 8, "m": 3, "stripe_unit": 4096}},
           "traffic": {"object_bytes": 4 * MIB}}
    ctx.update(over)
    return ctx


def test_roofline_reader():
    read = spec.reader("roofline_pct")
    args = {"work": "encode_hbm_bytes", "ops_counter": "ops"}
    assert read(_ctx(), **args) is None                  # no trace
    trace = {"busy_s": 0.01, "window_s": 5.0}
    assert read(_ctx(trace=trace), **args) is None       # no work
    got = read(_ctx(trace=trace, engine_traced={"ops": 100}), **args)
    least = 100 * 4 * MIB * 11 / 8 / 819e9
    assert got == pytest.approx(100 * least / 0.01)
    assert 0 < got < 100
    idle = {"busy_s": 0.0, "window_s": 5.0}
    assert read(_ctx(trace=idle, engine_traced={"ops": 100}),
                **args) is None


def test_stage_sum_reader():
    read = spec.reader("stage_sum_ms")
    stages = {"wire": {"sum_s": 2.0, "count": 100},
              "commit_reply": {"sum_s": 1.0, "count": 50},
              "never": {"sum_s": 0.0, "count": 0}}
    assert read(_ctx(stages=stages), stages=["wire", "commit_reply"]) \
        == pytest.approx(20.0 + 20.0)
    assert read(_ctx(stages=stages), stages=["never", "absent"]) \
        is None


def test_stat_ratio_reader():
    read = spec.reader("stat_ratio")
    ctx = _ctx(engine_window={"ops": 30, "flushes": 20})
    assert read(ctx, num="ops", den="flushes") == 1.5
    assert read(ctx, num="decode_ops", den="decode_flushes") is None


def test_quantile_is_the_smallest_value_covering_q():
    vals = sorted(float(v) for v in range(1, 101))
    assert quantile(vals, 0.95) == 95.0
    assert quantile(vals, 0.9) == 90.0
    assert quantile([7.0], 0.95) == 7.0
    assert quantile(sorted([1.0, 2.0, 3.0]), 0.5) == 2.0
    with pytest.raises(ValueError):
        quantile([], 0.5)


def test_payloads_come_from_the_seed_alone():
    big = 3_000_000_017                  # more than 32 signed bits hold
    assert seed_words(big) == [big & 0xFFFFFFFF, big >> 32]
    a, b = Payloads(big, 4096, 4), Payloads(big, 4096, 4)
    other = Payloads(big + 1, 4096, 4)
    assert a.of("w3_17") == b.of("w3_17")
    assert len({bytes(x) for x in a.buffers}) == 4
    assert a.buffers[0] != other.buffers[0]
    # a thread's consecutive objects take consecutive buffers
    assert a.of("w0_0") != a.of("w0_1")
    assert a.of("w0_0") == a.of("w0_4")
    with pytest.raises(ValueError):
        seed_words(-1)
