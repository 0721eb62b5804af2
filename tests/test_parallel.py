"""Sharded EC pipeline tests on the virtual 8-device CPU mesh."""

import time

import numpy as np
import pytest

from ceph_tpu.ops import gf256
from ceph_tpu.parallel import mesh as mesh_mod
from ceph_tpu.parallel import sharded_codec


@pytest.fixture(scope="module")
def mesh():
    import jax
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return mesh_mod.make_mesh(8)


def test_mesh_shape(mesh):
    assert mesh.shape["stripe"] * mesh.shape["shard"] == 8


def test_distributed_encode_matches_reference(mesh):
    k, m = 8, 3
    S, C = mesh.shape["stripe"] * 2, mesh.shape["shard"] * 64
    coding = gf256.rs_vandermonde_matrix(k, m)
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(S, k, C), dtype=np.uint8)

    step = sharded_codec.make_encode_step(mesh, coding)
    chunks, csum = step(sharded_codec.shard_stripe_batch(mesh, data))
    chunks = np.asarray(chunks)

    n_shard = mesh.shape["shard"]
    c_l = C // n_shard
    for s in range(S):
        want_parity = gf256.gf_matvec_chunks(coding, data[s])
        got = chunks[s, k:]  # parity after the ppermute placement shift
        # undo the ring shift: local block b of output came from block b-1
        unshifted = np.concatenate(
            [got[:, ((b - 1) % n_shard) * c_l:((b - 1) % n_shard + 1) * c_l]
             for b in range(n_shard)], axis=1)
        # got block b holds parity computed on block b-1's bytes
        restored = np.zeros_like(got)
        for b in range(n_shard):
            src = (b - 1) % n_shard
            restored[:, src * c_l:(src + 1) * c_l] = \
                got[:, b * c_l:(b + 1) * c_l]
        assert np.array_equal(restored, want_parity), s
        assert np.array_equal(chunks[s, :k], data[s])
    del unshifted
    # checksum: byte sums per chunk position over whole batch
    want_csum = np.zeros(k + m, dtype=np.uint64)
    want_csum[:k] = data.astype(np.uint64).sum(axis=(0, 2))
    assert np.array_equal(np.asarray(csum)[:k].astype(np.uint64), want_csum[:k])


def test_distributed_degraded_read(mesh):
    k, m = 4, 2
    S, C = 2, mesh.shape["shard"] * 32
    coding = gf256.rs_vandermonde_matrix(k, m)
    gen = gf256.systematic_generator(coding)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(S, k, C), dtype=np.uint8)
    all_chunks = np.stack(
        [np.concatenate([d, gf256.gf_matvec_chunks(coding, d)]) for d in data])

    lost = [1, 4]
    present = [0, 2, 3, 5]
    surv = all_chunks[:, present]
    step = sharded_codec.make_degraded_read_step(mesh, gen, present, lost)
    rec, full = step(sharded_codec.shard_stripe_batch(mesh, surv))
    assert np.array_equal(np.asarray(rec), all_chunks[:, lost])
    assert np.array_equal(np.asarray(full), all_chunks[:, lost])


def test_batcher_flush_routes_through_mesh(mesh):
    """VERDICT #8: the daemon's StripeBatcher flushes through the
    multi-chip encode step when a mesh is present — bit-exact vs the
    host codec, per-op slices preserved."""
    from ceph_tpu.models import registry as ec_registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_util import StripeBatcher, StripeInfo

    codec = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "jax"})
    cs = mesh.shape["shard"] * 64
    si = StripeInfo(stripe_width=4 * cs, chunk_size=cs)
    host = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "numpy"})
    rng = np.random.default_rng(7)
    b = StripeBatcher(si, codec, mesh=mesh)
    bufs = {}
    for op in range(3):
        data = rng.integers(0, 256, size=(op + 1) * si.stripe_width,
                            dtype=np.uint8)
        bufs[op] = data
        b.append(op, data)
    results = b.flush()
    assert len(results) == 3
    for op, shards, _crcs in results:
        want = ec_util.encode(si, host, bufs[op])
        for i in range(6):
            assert np.array_equal(shards[i], want[i]), (op, i)


def test_engine_uses_default_mesh(mesh):
    """The device engine picks up the process default mesh: flushes
    AT OR ABOVE the dense-vs-sharded threshold run the sharded encode
    step (multi-chip data plane engaged from the daemon seam), while
    smaller flushes stay on the single-chip path — both bit-exact."""
    from ceph_tpu.models import registry as ec_registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    from ceph_tpu.osd.ec_util import StripeInfo
    from ceph_tpu.parallel import mesh as mesh_mod

    codec = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "jax"})
    cs = mesh.shape["shard"] * 64
    si = StripeInfo(stripe_width=4 * cs, chunk_size=cs)
    rng = np.random.default_rng(8)
    big = rng.integers(0, 256, size=2 * si.stripe_width,
                       dtype=np.uint8)
    small = rng.integers(0, 256, size=si.stripe_width,
                         dtype=np.uint8)
    got = {}
    # threshold between the two payloads: the big flush routes
    # through the mesh, the small one stays dense
    eng = DeviceEncodeEngine(lambda key, fn: fn(),
                             mesh_flush_bytes=len(big))
    mesh_mod.set_default_mesh(mesh)
    try:
        eng.stage_encode("pg", codec, si, big,
                         lambda s, c, e: got.setdefault("big",
                                                        (s, e)))
        deadline = time.monotonic() + 15
        while "big" not in got and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.stats["mesh_flushes"] == 1, eng.stats
        eng.stage_encode("pg", codec, si, small,
                         lambda s, c, e: got.setdefault("small",
                                                        (s, e)))
        deadline = time.monotonic() + 15
        while "small" not in got and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        mesh_mod.set_default_mesh(None)
        eng.stop()
    assert eng.stats["mesh_flushes"] == 1, \
        (eng.stats, "sub-threshold flush must stay single-chip")
    host = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "numpy"})
    for name, payload in (("big", big), ("small", small)):
        assert name in got and got[name][1] is None, got
        want = ec_util.encode(si, host, payload)
        for i in range(6):
            assert np.array_equal(got[name][0][i], want[i]), (name, i)


def test_make_mesh_shard_cap_from_profile():
    """ISSUE 12 satellite: the shard-axis cap derives from the codec
    profile's chunk count when one is known (the flagship k=8,m=3
    profile wants all 8 devices on the shard axis — the hardcoded 4
    denied it); without a profile the historical cap of 4 holds."""
    m = mesh_mod.make_mesh(8, chunk_count=11)     # k=8,m=3
    assert dict(m.shape) == {"stripe": 1, "shard": 8}, dict(m.shape)
    m = mesh_mod.make_mesh(8)                     # no profile known
    assert dict(m.shape) == {"stripe": 2, "shard": 4}, dict(m.shape)
    m = mesh_mod.make_mesh(8, chunk_count=3)      # k=2,m=1
    assert dict(m.shape) == {"stripe": 4, "shard": 2}, dict(m.shape)
    # explicit factors still win over any cap
    m = mesh_mod.make_mesh(8, stripe=8, shard=1, chunk_count=11)
    assert dict(m.shape) == {"stripe": 8, "shard": 1}


def test_compile_seam_prefers_pjit_and_falls_back(mesh, monkeypatch):
    """The ISSUE 12 layout/compile seam: steps compile through the
    pjit route by default; forcing
    mesh_compile_mode=shard_map takes the explicit-collectives
    spelling — and BOTH produce bit-identical chunks and checksums."""
    k, m = 4, 2
    coding = gf256.rs_vandermonde_matrix(k, m)
    rng = np.random.default_rng(3)
    S, C = mesh.shape["stripe"] * 2, mesh.shape["shard"] * 32
    data = rng.integers(0, 256, size=(S, k, C), dtype=np.uint8)

    # degraded-read + scrub-verify twin inputs (shared across modes)
    gen = gf256.systematic_generator(coding)
    present, lost = [0, 2, 3, 5], [1, 4]
    full_chunks = np.stack(
        [np.concatenate([d, gf256.gf_matvec_chunks(coding, d)])
         for d in data])
    surv = np.ascontiguousarray(full_chunks[:, present])
    nobj = 8 * 2                          # divides the 8-device mesh
    l_b = 1 << 10
    vbatch = np.zeros((nobj, k + m, l_b), dtype=np.uint8)
    for i in range(nobj):
        vd = rng.integers(0, 256, (k, l_b), dtype=np.uint8)
        vbatch[i, :k] = vd
        vbatch[i, k:] = gf256.gf_matvec_chunks(coding, vd)
    vbatch[3, 0, 5] ^= 1                  # one rotten row

    outs = {}
    for mode in ("pjit", "shard_map"):
        monkeypatch.setenv("CEPH_TPU_MESH_COMPILE_MODE", mode)
        step = sharded_codec.make_encode_step(mesh, coding)
        assert step.compile_path == mode, (mode, step.compile_path)
        chunks, csum = step(sharded_codec.shard_stripe_batch(mesh,
                                                             data))
        dstep = sharded_codec.make_degraded_read_step(
            mesh, gen, present, lost)
        rec, gathered = dstep(
            sharded_codec.shard_stripe_batch(mesh, surv))
        vstep = sharded_codec.make_verify_step(mesh, coding, k)
        mism, lin = vstep(
            sharded_codec.shard_object_batch(mesh, vbatch))
        outs[mode] = tuple(np.asarray(x) for x in
                           (chunks, csum, rec, gathered, mism, lin))
    for a, b in zip(outs["pjit"], outs["shard_map"]):
        assert np.array_equal(a, b)
    # ...and the twins are right, not just mutually consistent
    _, _, rec, gathered, mism, _lin = outs["pjit"]
    assert np.array_equal(rec, full_chunks[:, lost])
    assert np.array_equal(gathered, full_chunks[:, lost])
    assert mism[3].any() and not mism[0].any()
    # both seam paths accounted
    from ceph_tpu.utils.device_telemetry import telemetry
    counters = telemetry().perf.dump()
    assert counters.get("mesh_compile_pjit", 0) >= 1
    assert counters.get("mesh_compile_shard_map", 0) >= 1


def test_placement_map_deterministic_and_disjoint(mesh):
    """PG→chip placement: a pure, CRUSH-stable function of (pgid,
    mesh) — identical across map instances (the restart-stability
    contract) — with slot submeshes that partition the device set."""
    from ceph_tpu.parallel import placement

    pmap = placement.PlacementMap(mesh)
    pmap2 = placement.PlacementMap(mesh_mod.make_mesh(8))
    pgids = [(7, ps) for ps in range(32)] + [(3, ps) for ps in
                                             range(8)]
    assert [pmap.slot(p) for p in pgids] == \
        [pmap2.slot(p) for p in pgids]
    # the hash is pinned: a silent change would remap every PG's
    # chips on upgrade (the placement-map contract, BASELINE.md)
    assert placement.stable_hash((7, 0)) == \
        placement.stable_hash("(7, 0)")
    assert [pmap.slot((7, ps)) for ps in range(8)] == \
        [placement.stable_hash((7, ps)) % pmap.n_slots
         for ps in range(8)]
    # both slots exercised over a few dozen pgids
    assert {pmap.slot(p) for p in pgids} == set(range(pmap.n_slots))
    # submeshes: one stripe row each, disjoint, union = all devices
    seen = set()
    for slot in range(pmap.n_slots):
        sm = pmap.submesh(slot)
        assert dict(sm.shape) == {"stripe": 1,
                                  "shard": mesh.shape["shard"]}
        devs = {id(d) for d in sm.devices.ravel()}
        assert not (devs & seen), "slot submeshes overlap"
        seen |= devs
        # cached: same slot -> same Mesh object (step caches key by
        # mesh identity)
        assert pmap.submesh(slot) is sm
    assert seen == {id(d) for d in mesh.devices.ravel()}


def test_flush_decode_mesh_bit_exact(mesh):
    """The engine's multi-chip decode twin (ec_util.flush_decode_mesh)
    reconstructs bit-exactly vs the host corpus — present rows
    verbatim, missing rows through the sharded decode matmul."""
    from ceph_tpu.models import registry as ec_registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_util import StripeInfo

    codec = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "jax"})
    host = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "numpy"})
    cs = mesh.shape["shard"] * 64
    si = StripeInfo(stripe_width=4 * cs, chunk_size=cs)
    rng = np.random.default_rng(17)
    payload = rng.integers(0, 256, 5 * si.stripe_width,
                           dtype=np.uint8)
    shards = ec_util.encode(si, host, payload)
    lost = [1, 4]
    surv = {i: v for i, v in shards.items() if i not in lost}
    want = [1, 2, 4]                     # mix of missing + present
    got = ec_util.flush_decode_mesh(mesh, si, codec, surv, want)
    for c in want:
        assert np.array_equal(got[c], shards[c]), c


def test_verify_step_mesh_twin_bit_exact(mesh):
    """The deep-scrub mesh twin returns the same mismatch bitmap and
    crc linear parts as the single-chip fused program, including on
    zero-padded object rows."""
    from ceph_tpu.osd import scrub_engine

    k, m = 4, 2
    mat = gf256.rs_vandermonde_matrix(k, m)
    rng = np.random.default_rng(23)
    nobj, l_b = 5, 1 << 12               # pads to 8 for the mesh
    batch = np.zeros((nobj, k + m, l_b), dtype=np.uint8)
    for i in range(nobj):
        data = rng.integers(0, 256, (k, l_b), dtype=np.uint8)
        batch[i, :k] = data
        batch[i, k:] = gf256.gf_matvec_chunks(mat, data)
    batch[2, 1, 100] ^= 0x40             # one silent bit flip
    mism_host, lin_host = scrub_engine.verify_batch(mat, k, batch)
    mism_mesh, lin_mesh = scrub_engine.verify_batch(mat, k, batch,
                                                    mesh=mesh)
    assert np.array_equal(mism_host, mism_mesh)
    assert np.array_equal(lin_host, lin_mesh)
    assert mism_mesh[2].any() and not mism_mesh[0].any()
    from ceph_tpu.utils.device_telemetry import telemetry
    assert telemetry().perf.dump().get("mesh_scrub_batches", 0) >= 1


def test_engine_decode_routes_through_mesh(mesh, monkeypatch):
    """stage_decode on a default mesh: a signature-batched decode at
    or above the crossover rides the mesh twin (mesh_decode_flushes),
    bit-exact vs the host twin."""
    from ceph_tpu.models import registry as ec_registry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.device_engine import DeviceEncodeEngine
    from ceph_tpu.osd.ec_util import StripeInfo

    monkeypatch.setenv("CEPH_TPU_MESH_FLUSH_BYTES", "1")
    codec = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "jax"})
    host = ec_registry.instance().factory(
        "jerasure", {"plugin": "jerasure", "k": "4", "m": "2",
                     "backend": "numpy"})
    cs = mesh.shape["shard"] * 64
    si = StripeInfo(stripe_width=4 * cs, chunk_size=cs)
    rng = np.random.default_rng(29)
    payload = rng.integers(0, 256, 3 * si.stripe_width,
                           dtype=np.uint8)
    shards = ec_util.encode(si, host, payload)
    surv = {i: v for i, v in shards.items() if i != 0}
    eng = DeviceEncodeEngine(lambda key, fn: fn())
    mesh_mod.set_default_mesh(mesh)
    try:
        out = eng.decode_sync("pg-dec", codec, si, surv, [0])
    finally:
        mesh_mod.set_default_mesh(None)
        eng.stop()
    assert out is not None and np.array_equal(out[0], shards[0])
    assert eng.stats["mesh_decode_flushes"] == 1, eng.stats


def test_distributed_clay_repair(mesh):
    """Clay single-node repair as a mesh collective: helper sub-chunk
    fragments shard over the mesh, the linearized repair matrix
    (models/clay.py _repair_matrix) reconstructs the lost chunk, and
    an all_gather reassembles it — bit-exact vs the host repair."""
    from ceph_tpu.models import registry as ec_registry

    codec = ec_registry.instance().factory(
        "clay", {"plugin": "clay", "k": "4", "m": "2",
                 "backend": "numpy"})
    ssc = codec.get_sub_chunk_count()
    rss = ssc // codec.q
    sub = mesh.shape["shard"] * 16          # bytes per sub-chunk
    cs = ssc * sub
    rng = np.random.default_rng(9)
    data = {i: rng.integers(0, 256, cs, dtype=np.uint8)
            for i in range(4)}
    enc = codec.encode_chunks(list(range(6)), data)
    chunks = {**{i: np.asarray(data[i]) for i in range(4)},
              **{i: np.asarray(v) for i, v in enc.items()}}
    lost = 2
    helpers = tuple(i for i in range(6) if i != lost)
    # helper fragments: the repair sub-chunk ranges of each helper
    ranges = codec.get_repair_subchunks(lost)
    frag = {h: np.concatenate([
        chunks[h][off * sub:(off + cnt) * sub]
        for off, cnt in ranges]) for h in helpers}
    # host oracle
    want = codec.decode([lost], {h: f for h, f in frag.items()}, cs)
    mat = codec._repair_matrix(lost, helpers)
    # distribute: stack fragments as rows [S=1, H*rss, sub]
    x = np.stack([f.reshape(rss, sub) for h, f in
                  sorted(frag.items())]).reshape(1, len(helpers) * rss,
                                                 sub)
    # one logical stripe replicated across the stripe axis (the axis
    # must divide S; real batches carry many stripes)
    x = np.repeat(x, mesh.shape["stripe"], axis=0)
    step = sharded_codec.make_matrix_step(mesh, mat)
    rec, full = step(sharded_codec.shard_stripe_batch(mesh, x))
    got = np.asarray(full)[0].reshape(-1)
    assert np.array_equal(got, np.asarray(want[lost])), "clay mesh repair"
