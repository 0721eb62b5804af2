"""The main path's kernels, compiled for the chip without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED
``v5e:2x2`` topology (no device attached, ``JAX_PLATFORMS=cpu``), so a
kernel Mosaic would refuse on the machine with the chip is refused
here first, at no chip time: the GF matvec at the encode/decode
matrices of the served profiles, the crc row kernel, the fused
encode+crc flush program at the buckets ``chip_smoke.py`` really
produces, the XOR-schedule encode, one block-sparse plan and the Clay
k=8,m=4,d=11 encode and layered-repair kernels, the Clay pool's served
flush programs (layered encode+crc, table-operand decode) at the cells'
buckets, and the mesh steps for the 2x2 mesh. A compile that passes is not a chip run: nothing
executes, so this says nothing about results or times.

One file, on purpose: the process that describes the topology holds
the TPU library's lock until it exits, so a second file could land on
another xdist worker and skip. The topology is described inside a
module-scoped fixture (never at import, never autouse) that skips
when it cannot be described.
"""

import inspect
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without a chip: keep it off
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if not had_log_dir:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` at trace time still
    sees the CPU here and would take its CPU branch (the Pallas
    interpreter, the plain-XLA crc). Steer it to the chip's branch in
    the test, not through an option of the program."""
    from ceph_tpu.ops import crc32c_device as cd
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cd._jit_linear_batch.cache_clear()
    yield
    cd._jit_linear_batch.cache_clear()


def _u8(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sharding)


def _compile(fn, *args):
    compiled = (fn if hasattr(fn, "lower") else jax.jit(fn)) \
        .lower(*args).compile()
    return compiled, compiled.as_text()


def _codec(plugin="jerasure", **profile):
    from ceph_tpu.models.registry import instance
    return instance().factory(
        plugin, {"plugin": plugin,
                 **{a: str(b) for a, b in profile.items()}})


# -- GF matvec: encode and the e=1/e=2 decode matrices -----------------

@pytest.mark.parametrize("m_out,k", [
    (3, 8), (1, 8), (2, 8),     # flagship k=8,m=3: encode, 1/2 erased
    (4, 4), (2, 4), (3, 6)])
def test_gf_pallas_matvec(one_chip, m_out, k):
    from ceph_tpu.ops import gf_pallas
    n = 1 << 20
    g = gf_pallas._fold(k)
    tile = gf_pallas.DEFAULT_TILE // g
    bmat = jax.ShapeDtypeStruct((g * 8 * m_out, g * 8 * k), jnp.int32,
                                sharding=one_chip)
    _, text = _compile(gf_pallas._matvec_padded, bmat,
                       _u8((k, n), one_chip), k, m_out, g, tile)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m_out", [1, 2])
@pytest.mark.parametrize("n", [1 << 19, 1 << 23])
def test_gf_pallas_decode_buckets_donated(one_chip, m_out, n):
    """The degraded-read matvec as the engine launches it: host input,
    so the donating twin, at the smallest and largest byte bucket 16
    concurrent 4 MiB reads produce."""
    from ceph_tpu.ops import gf_pallas
    k, g = 8, gf_pallas._fold(8)
    bmat = jax.ShapeDtypeStruct((g * 8 * m_out, g * 8 * k), jnp.int32,
                                sharding=one_chip)
    _, text = _compile(gf_pallas._matvec_padded_donated, bmat,
                       _u8((k, n), one_chip), k, m_out, g,
                       gf_pallas.DEFAULT_TILE // g)
    assert "tpu_custom_call" in text


def test_gf_xor_pallas_encode(one_chip):
    """The XOR-schedule encode of k=8,m=3 on 1 MiB chunks in strip
    layout ([8k, C/4096, 128] int32)."""
    from ceph_tpu.ops import gf_xor_pallas
    mat = np.asarray(_codec(k=8, m=3, backend="numpy").coding_matrix)
    kernel = gf_xor_pallas.get_kernel(mat)
    strips = jax.ShapeDtypeStruct((64, (1 << 20) // 4096, 128),
                                  jnp.int32, sharding=one_chip)
    _, text = _compile(kernel.encode_strips, strips)
    assert "tpu_custom_call" in text


# -- crc ---------------------------------------------------------------

def test_crc_row_kernel(one_chip):
    from ceph_tpu.ops import crc32c_device as cd
    rows = 8 * cd._G * cd._TR
    b_mat = jax.ShapeDtypeStruct(
        (cd._G * cd.ROW_BYTES * 8, cd._G * 32), jnp.int8,
        sharding=one_chip)
    _, text = _compile(cd._pallas_rows_fn(),
                       _u8((rows, cd.ROW_BYTES), one_chip), b_mat,
                       rows)
    assert "tpu_custom_call" in text


def test_crc_linear_batch_takes_the_pallas_branch(one_chip, as_tpu):
    from ceph_tpu.ops import crc32c_device as cd
    _, text = _compile(cd.crc_linear_device,
                       _u8((11, 512 << 10), one_chip))
    assert "tpu_custom_call" in text


# -- the fused encode+crc flush program --------------------------------

#: (ops in the flush, bytes per shard per op): what 16 writers of
#: 4 MiB objects on k=8 produce (512 KiB per shard per op; the engine
#: flushes 1..16 ops), read off the CPU rehearsal of chip_smoke.py
FLUSHES = [(1, 512 << 10), (2, 512 << 10), (3, 512 << 10),
           (8, 512 << 10), (16, 512 << 10)]


@pytest.mark.parametrize("n_ops,op_len", FLUSHES)
def test_fused_flush_program(one_chip, as_tpu, n_ops, op_len):
    from ceph_tpu.osd import ec_util
    codec = _codec(k=8, m=3, backend="pallas")
    n_b, lmax_b, nops_b = ec_util.fused_buckets(
        n_ops * op_len, op_len, n_ops)
    fn, _new = ec_util.fused_program(codec, n_b, lmax_b, nops_b)
    idx = jax.ShapeDtypeStruct((nops_b,), jnp.int32,
                               sharding=one_chip)
    compiled, text = _compile(fn, _u8((8, n_b), one_chip), idx, idx)
    # both kernels are in the one program: GF matvec + crc rows
    assert text.count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    # three flushes in flight must fit the chip's 16 GB with room
    assert total < 4 << 30, (n_ops, total)


def test_fused_buckets_of_the_smoke_traffic():
    """The bucket ladder the tests above compile IS the one the flush
    path computes for the smoke's traffic."""
    from ceph_tpu.osd import ec_util
    got = {ec_util.fused_buckets(n * (512 << 10), 512 << 10, n)
           for n in range(1, 17)}
    assert got == {(512 << 10, 512 << 10, 1), (1 << 20, 512 << 10, 2),
                   (2 << 20, 512 << 10, 4), (4 << 20, 512 << 10, 8),
                   (8 << 20, 512 << 10, 16)}


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2)])
def test_overwrite_flush_program(one_chip, k, m):
    """The overwrite route's program (the fused program without crcs:
    the GF encode alone) at the one bucket every overwrite flush of
    up to 16 stripes of 4 KiB chunks takes."""
    from ceph_tpu.osd import ec_util
    codec = _codec(k=k, m=m, backend="pallas")
    n_b = ec_util._pow2_bucket(16 * 4096, ec_util.OVERWRITE_BUCKET)
    assert n_b == ec_util._pow2_bucket(4096, ec_util.OVERWRITE_BUCKET)
    fn, _new = ec_util.fused_program(codec, n_b, 1, 1, with_crcs=False)
    idx = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _, text = _compile(fn, _u8((k, n_b), one_chip), idx, idx)
    assert text.count("tpu_custom_call") == 1


# -- block-sparse and Clay: interpret= steered to Mosaic ----------------

def _clay():
    return _codec("clay", k=8, m=4, d=11, backend="numpy")


def test_block_sparse_plan_of_the_clay_decode2_matrix(one_chip,
                                                      as_tpu):
    from ceph_tpu.ops import gf_block_sparse as bs
    mat = _clay()._decode_matrix(tuple(range(2, 12)), (0, 1))
    fn = lambda data: bs.matvec_device(mat, data)
    _, text = _compile(fn, _u8((mat.shape[1], 1 << 15), one_chip))
    assert "tpu_custom_call" in text


def test_clay_encode_kernel(one_chip, as_tpu):
    from ceph_tpu.models.clay_device import build_encode_kernel
    codec = _clay()
    enc = build_encode_kernel(codec)
    _, text = _compile(
        enc, _u8((codec.k, codec.sub_chunk_no, 4096), one_chip))
    assert "tpu_custom_call" in text


def test_clay_repair_kernel(one_chip, as_tpu):
    """The layered-decode kernel for one lost node (padded to m nodes
    the way _decode_layered pads it)."""
    from ceph_tpu.models.clay_device import build_transform_kernel
    codec = _clay()
    qt = codec.q * codec.t
    erased = {codec._node_id(0)}
    for node in range(codec.k + codec.nu, qt):
        if len(erased) >= codec.m:
            break
        erased.add(node)
    fn = build_transform_kernel(codec, frozenset(erased))
    _, text = _compile(
        fn, _u8((qt, codec.sub_chunk_no, 2048), one_chip))
    assert "tpu_custom_call" in text


# -- the Clay pool's served flush programs (ISSUE 29) --------------------

def _clay_served():
    return _codec("clay", k=8, m=4, d=11, scalar_mds="jerasure",
                  technique="reed_sol_van", backend="pallas")


@pytest.mark.parametrize("n_ops", [1, 16])
def test_clay_layered_flush_program(one_chip, as_tpu, n_ops):
    """The one program a Clay flush is: the staged batch of ``n_ops``
    4 MiB objects turned plane-major on the device, the layered encode
    kernel, the parity turned back, the crc rows of all 12 shards; at
    the smallest and the largest bucket the write cell's warm-up
    bursts compile."""
    from ceph_tpu.osd import ec_util
    codec, op_len, unit = _clay_served(), 512 << 10, 4096
    n_b, lmax_b, nops_b = ec_util.fused_buckets(
        n_ops * op_len, op_len, n_ops)
    fn, _new = ec_util.layered_program(codec, unit, n_b // unit,
                                       lmax_b, nops_b, True)
    idx = jax.ShapeDtypeStruct((nops_b,), jnp.int32,
                               sharding=one_chip)
    compiled, text = _compile(fn, _u8((n_b * 8,), one_chip), idx, idx)
    # the layered encode kernel and the crc rows, in one program
    assert text.count("tpu_custom_call") >= 2
    assert "clay_encode" in text
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    # three flushes in flight must fit the chip's 16 GB with room
    assert total < 4 << 30, (n_ops, total)


@pytest.mark.parametrize("n_ops,lost", [(1, 1), (16, 2)])
def test_clay_layered_decode_program(one_chip, n_ops, lost):
    """One decode program a shape bucket: the signature's table is an
    operand (int8 bit matrix), so nothing of a signature is compiled
    in; one lost shard at one read, two at sixteen."""
    from ceph_tpu.osd import ec_util
    codec, unit = _clay_served(), 4096
    n_b = ec_util._pow2_bucket(n_ops * (512 << 10), 1 << 14)
    fn, _new = ec_util.layered_decode_program(codec, unit, n_b, lost)
    table = jax.ShapeDtypeStruct((8 * lost * 64, 8 * 8 * 64), jnp.int8,
                                 sharding=one_chip)
    compiled, _text = _compile(fn, _u8((8, n_b), one_chip), table)
    mem = compiled.memory_analysis()
    # the rebuilt shards alone come back (rows padded to the tiling)
    assert lost * n_b <= mem.output_size_in_bytes <= 4 * n_b
    assert mem.temp_size_in_bytes < 2 << 30, (n_ops, lost)


# -- the 2x2 mesh: what chip_smoke.py --chips 4 runs ---------------------

@pytest.fixture(scope="module")
def mesh(topo):
    from ceph_tpu.parallel import mesh as mesh_mod
    return mesh_mod.make_mesh(devices=list(topo.devices),
                              chunk_count=11)


def test_mesh_spans_the_four_described_devices(mesh):
    assert isinstance(mesh, Mesh)
    assert dict(mesh.shape) == {"stripe": 1, "shard": 4}
    assert len(set(mesh.devices.flat)) == 4


@pytest.fixture
def shapes_for_arrays(monkeypatch):
    """There is no device to hold an array: where a step builder
    uploads its matrix, hand it the shape (with its sharding)."""
    monkeypatch.setattr(
        jax, "device_put",
        lambda x, sharding: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding))


def _compile_mesh_step(mesh, step, n_stripes):
    """Lower the step's jitted program with the matrix it bound
    (``_finish_step``'s closure, behind the telemetry wrapper)."""
    from ceph_tpu.parallel.mesh_compile import LAYOUT
    bound = inspect.getclosurevars(step.__wrapped__).nonlocals
    data = _u8((n_stripes, 8, 4096),
               NamedSharding(mesh, LAYOUT.stage_batch()))
    return _compile(bound["compiled"], bound["bmat_dev"], data)


#: stripes of 8 x 4 KiB: the 64 MiB batch of the sharded steps, and
#: the 16 MiB engine flush (4 ops of 4 MiB) of ``--chips 4``
@pytest.mark.parametrize("n_stripes", [2048, 512])
def test_mesh_encode_step(mesh, shapes_for_arrays, n_stripes):
    from ceph_tpu.parallel import sharded_codec
    mat = np.asarray(_codec(k=8, m=3, backend="numpy").coding_matrix)
    step = sharded_codec.make_encode_step(mesh, mat, place=False)
    compiled, _ = _compile_mesh_step(mesh, step, n_stripes)
    chunks, _csum = compiled.output_shardings
    assert len(chunks.device_set) == 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 << 30


def test_mesh_degraded_read_step(mesh, shapes_for_arrays):
    from ceph_tpu.ops import gf256
    from ceph_tpu.parallel import sharded_codec
    mat = np.asarray(_codec(k=8, m=3, backend="numpy").coding_matrix)
    lost = [1, 5]
    present = [i for i in range(11) if i not in lost][:8]
    step = sharded_codec.make_degraded_read_step(
        mesh, gf256.systematic_generator(mat), present, lost)
    compiled, text = _compile_mesh_step(mesh, step, 2048)
    rec, _full = compiled.output_shardings
    assert len(rec.device_set) == 4
    # the gathered second output is the step's one collective
    assert "all-gather" in text
