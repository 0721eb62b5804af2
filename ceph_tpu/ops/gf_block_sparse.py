"""Block-sparse Pallas TPU kernel for GF(2^8) matrix-stripe multiply.

The Clay linearized signature matrices (models/clay.py) are big and
SPARSE: the k=8,m=4,d=11 decode-2 matrix is [128, 640] GF entries at
~8% byte density / ~4% bit density, yet the dense device path
(ops/gf_jax bit-sliced matmul) streams all 1024x5120 bit-MACs per lane
— the measured reason decode tops out at 14.4 GB/s while the
structured encode kernel does 525 (BASELINE.md r5 bisect). This module
is the skip-the-zeros program-optimization approach of
"Accelerating XOR-based Erasure Coding using Program Optimization
Techniques" (arXiv:2108.02692) applied to MXU tiles instead of CPU
XOR schedules:

- ``plan_blocks`` partitions the matrix into [tile_m, tile_k] GF
  blocks and keeps only the occupied ones. Row blocks are formed by
  GREEDY SUPPORT CLUSTERING (rows sharing column support land in the
  same group), because the MXU cost of a matmul is
  ceil(bit_rows/128) * bit_depth: a group whose 8*tile_m = 128 bit
  rows share their column blocks turns the occupancy saving into a
  real cycle saving instead of idling half the systolic array.
  Measured on the clay decode-2 matrix: identity grouping 2.1x,
  clustered 3.3x MAC cut at [16, 8] blocks (6.2x at byte granularity
  — the gap is block padding).
- the kernel gathers, per row group, ONLY the occupied column blocks'
  data rows (static concat of 8-row-aligned slices), bit-expands the
  gathered [G, T] tile in VMEM, and runs one [128, 8G] bit-matmul per
  group — a gather-of-blocks matmul sharing the nibble-fold layout of
  ops/gf_pallas (``_permute_bitmatrix``: bit planes c-major over
  gathered bytes), so accumulator exactness arguments carry over
  unchanged (0/1 bf16 products, f32 sums < 2^24).

The plan (row permutation + per-group block lists + compacted
bit-matrices) is host-side and cached per matrix content; output rows
come back group-major and are un-permuted by one XLA gather outside
the kernel. All-zero column blocks are never touched — for the clay
matrices that also skips ~20% of input rows entirely.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from ceph_tpu.utils.lru import BoundedLRU

#: GF rows per row group: 8*16 = 128 bit rows — exactly the MXU's
#: output-row capacity, so every group matmul fills the array
TILE_M = 16

#: GF columns per column block: 8-row gather slices stay sublane-
#: aligned for the int32 working tile (Mosaic (8, 128) tiling)
TILE_K = 8

#: lane tile per grid step
DEFAULT_TILE = 512

#: plan cache bound (decode signatures are C(k+m, <=m) per codec; the
#: same sizing argument as the ISA decode-table LRU)
_PLAN_CACHE_SIZE = 64


@dataclass
class BlockPlan:
    """Host-side gather-of-blocks schedule for one GF matrix."""

    m: int                       # GF output rows (unpadded)
    k: int                       # GF input rows (unpadded)
    kp: int                      # input rows padded to tile_k
    tile_m: int
    tile_k: int
    row_order: np.ndarray        # [mp] group-major original-row ids
    inv_order: np.ndarray        # [m] output row -> group-major slot
    groups: list                 # [(block_col_ids, bitmat [8tm, 8G])]
    occupancy: float             # occupied / total blocks
    mac_frac: float              # sparse bit-MACs / dense bit-MACs
    cost_frac: float             # MXU cost (row-pass * depth) ratio

    @property
    def worthwhile(self) -> bool:
        """Whether the schedule saves real MXU cycles (guards the
        'where density allows' call sites): a nearly-dense matrix
        gains nothing and pays the gather overhead."""
        return self.cost_frac <= 0.7


def _support(mat: np.ndarray, tile_k: int) -> list:
    """Per-row frozenset of occupied column-block ids."""
    m, kp = mat.shape
    nb = kp // tile_k
    blocked = mat.reshape(m, nb, tile_k).any(axis=2)
    return [frozenset(np.nonzero(blocked[r])[0].tolist())
            for r in range(m)]


def _cluster_rows(sup: list, tile_m: int) -> list:
    """Greedy support clustering: groups of tile_m rows minimizing
    each group's union of occupied column blocks (what the group's
    matmul depth is proportional to)."""
    remaining = set(range(len(sup)))
    groups = []
    while remaining:
        seed = max(remaining, key=lambda r: (len(sup[r]), -r))
        grp = [seed]
        remaining.discard(seed)
        union = set(sup[seed])
        while len(grp) < tile_m and remaining:
            best = min(remaining,
                       key=lambda r: (len(sup[r] - union),
                                      -len(sup[r] & union), r))
            grp.append(best)
            remaining.discard(best)
            union |= sup[best]
        groups.append(sorted(grp))
    return groups


def plan_blocks(mat: np.ndarray, tile_m: int = TILE_M,
                tile_k: int = TILE_K) -> BlockPlan:
    """Build the gather-of-blocks schedule for ``mat`` [m, k] uint8."""
    from ceph_tpu.ops.gf_pallas import _permute_bitmatrix

    mat = np.asarray(mat, dtype=np.uint8)
    m, k = mat.shape
    kp = -(-k // tile_k) * tile_k
    mp = -(-m // tile_m) * tile_m
    padded = np.zeros((mp, kp), dtype=np.uint8)
    padded[:m, :k] = mat
    sup = _support(padded, tile_k)
    # padding rows have empty support and cluster into the emptiest
    # group for free
    clusters = _cluster_rows(sup[:m], tile_m)
    # pad the last group with virtual zero rows
    flat: list[int] = []
    for grp in clusters:
        flat.extend(grp)
    while len(flat) < mp:
        flat.append(len(flat))          # virtual padding row ids
    row_order = np.asarray(flat, dtype=np.int64)
    inv_order = np.empty(m, dtype=np.int64)
    for slot, r in enumerate(flat):
        if r < m:
            inv_order[r] = slot

    groups = []
    occupied = 0
    cost = 0
    for gi in range(mp // tile_m):
        rows = row_order[gi * tile_m:(gi + 1) * tile_m]
        sub = padded[rows]               # [tile_m, kp]
        nb = kp // tile_k
        occ = np.nonzero(
            sub.reshape(tile_m, nb, tile_k).any(axis=(0, 2)))[0]
        occupied += len(occ)
        cost += len(occ) * 8 * tile_k    # one row pass per group
        if len(occ):
            compact = np.concatenate(
                [sub[:, b * tile_k:(b + 1) * tile_k] for b in occ],
                axis=1)                  # [tile_m, G]
            bitmat = _permute_bitmatrix(compact).astype(np.float32)
        else:
            bitmat = None
        groups.append((occ.astype(np.int64), bitmat))
    total_blocks = (mp // tile_m) * (kp // tile_k)
    dense_cost = (mp // tile_m) * -(-8 * tile_m // 128) * 8 * kp
    return BlockPlan(
        m=m, k=k, kp=kp, tile_m=tile_m, tile_k=tile_k,
        row_order=row_order, inv_order=inv_order, groups=groups,
        occupancy=occupied / max(total_blocks, 1),
        mac_frac=(occupied * 8 * tile_m * 8 * tile_k)
        / max(8 * mp * 8 * kp, 1),
        cost_frac=cost * -(-8 * tile_m // 128) / max(dense_cost, 1))


def occupancy_stats(mat: np.ndarray, tile_m: int = TILE_M,
                    tile_k: int = TILE_K) -> dict:
    """Density numbers for BASELINE.md / bench reporting."""
    plan = plan_blocks(mat, tile_m, tile_k)
    mat = np.asarray(mat, dtype=np.uint8)
    return {
        "shape": list(mat.shape),
        "byte_density": round(float((mat != 0).mean()), 4),
        "block_occupancy": round(plan.occupancy, 4),
        "mac_frac": round(plan.mac_frac, 4),
        "cost_frac": round(plan.cost_frac, 4),
        "mac_cut": round(1.0 / max(plan.cost_frac, 1e-9), 2),
    }


# -- kernel -------------------------------------------------------------

def _sparse_kernel(data_ref, *refs, plan: BlockPlan):
    """One lane tile: per row group, gather occupied column blocks,
    bit-expand, one [8*tile_m, 8G] matmul, VPU pack. ``refs`` carries
    one bit-matrix ref per non-empty group, then out_ref last."""
    import jax
    import jax.numpy as jnp

    out_ref = refs[-1]
    mat_refs = refs[:-1]
    tm, tk = plan.tile_m, plan.tile_k
    c32 = data_ref[:].astype(jnp.int32)            # [kp, T]
    w = jnp.left_shift(
        1, jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0))
    outs = []
    ri = 0
    for occ, _bitmat in plan.groups:
        if not len(occ):
            outs.append(jnp.zeros((tm, c32.shape[1]), jnp.uint8))
            continue
        gathered = jnp.concatenate(
            [c32[int(b) * tk:(int(b) + 1) * tk] for b in occ],
            axis=0)                                # [G, T]
        bits = jnp.concatenate(
            [(gathered >> c) & 1 for c in range(8)],
            axis=0)                                # [8G, T] c-major
        acc = jax.lax.dot_general(
            mat_refs[ri][:].astype(jnp.bfloat16),
            bits.astype(jnp.bfloat16),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ri += 1
        b = acc.astype(jnp.int32) & 1              # [8*tm, T]
        rows = [jnp.sum(b[8 * i:8 * i + 8] * w, axis=0, keepdims=True)
                for i in range(tm)]
        outs.append(jnp.concatenate(rows, axis=0).astype(jnp.uint8))
    out_ref[:] = jnp.concatenate(outs, axis=0)     # group-major rows


def _build_runner(plan: BlockPlan, tile: int, sig: str = ""):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    mats = [g[1] for g in plan.groups if g[1] is not None]
    mp = len(plan.groups) * plan.tile_m
    whole = lambda shape: pl.BlockSpec(
        shape, lambda i: (0, 0), memory_space=pltpu.VMEM)

    @functools.partial(jax.jit, static_argnames=("n",))
    def run_padded(data, *mat_args, n):
        grid = (n // tile,)
        return pl.pallas_call(
            functools.partial(_sparse_kernel, plan=plan),
            grid=grid,
            in_specs=[pl.BlockSpec((plan.kp, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM)] +
                     [whole(m2.shape) for m2 in mat_args],
            out_specs=pl.BlockSpec((mp, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((mp, n), jnp.uint8),
            compiler_params=pltpu.CompilerParams(
                # gathered bit tiles + per-group compacted matrices
                # exceed the 16 MiB default scoped budget at larger
                # lane tiles; same headroom raise as the clay kernels
                vmem_limit_bytes=64 * 1024 * 1024,
            ),
            interpret=jax.default_backend() == "cpu",
        )(data, *mat_args)

    inv = jnp.asarray(plan.inv_order)

    def runner(data):
        data = jnp.asarray(data, dtype=jnp.uint8)
        n = data.shape[1]
        if plan.kp != data.shape[0]:
            data = jnp.pad(data, ((0, plan.kp - data.shape[0]),
                                  (0, 0)))
        nb = tile
        while nb < n:
            nb <<= 1
        if nb != n:
            data = jnp.pad(data, ((0, 0), (0, nb - n)))
        mat_args = [jnp.asarray(m2) for m2 in mats]
        from ceph_tpu.ops.jax_util import tracing_active
        if tracing_active():
            out = run_padded(data, *mat_args, n=nb)
        else:
            from ceph_tpu.utils.device_telemetry import telemetry
            out = telemetry().timed_call(
                f"{sig}N{nb}", run_padded, data, *mat_args, n=nb)
        # un-permute the group-major rows with one XLA gather (out is
        # the small side: e*ssc rows vs a*ssc input rows)
        out = jnp.take(out, inv, axis=0)
        return out[:, :n] if nb != n else out

    return runner


class _RunnerCache:
    """(matrix bytes, tiles) -> (plan, runner), LRU-bounded like the
    linearized-transform cache it sits next to in models/clay.py."""

    def __init__(self) -> None:
        self._lru = BoundedLRU(_PLAN_CACHE_SIZE)

    def get(self, mat: np.ndarray, tile_m: int, tile_k: int,
            tile: int):
        mat = np.asarray(mat, dtype=np.uint8)
        key = (mat.shape, tile_m, tile_k, tile, mat.tobytes())

        def build():
            import zlib
            plan = plan_blocks(mat, tile_m, tile_k)
            # matrix-content digest in the signature: two same-shape
            # matrices compile two DIFFERENT programs, which must not
            # read as a recompile of one signature
            sig = (f"gf_block_sparse[{plan.m}x{plan.k}]"
                   f"#{zlib.crc32(mat.tobytes()):08x}t{tile}")
            return plan, _build_runner(plan, tile, sig)

        return self._lru.get_or_build(key, build)


_runner_cache = _RunnerCache()


def matvec_device(mat: np.ndarray, data, tile_m: int = TILE_M,
                  tile_k: int = TILE_K, tile: int = DEFAULT_TILE):
    """Device-in/device-out block-sparse GF matvec.

    mat: [m, k] uint8 (host). data: [k, N] uint8 (jax or numpy).
    Returns a device array [m, N] uint8, byte-identical to the dense
    oracle (zero blocks contribute nothing over GF).
    """
    _plan, runner = _runner_cache.get(mat, tile_m, tile_k, tile)
    return runner(data)


def matvec(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Host-in/host-out wrapper (ops.backend matvec contract)."""
    import jax
    return np.asarray(jax.device_get(matvec_device(mat, data)))


def plan_for(mat: np.ndarray, tile_m: int = TILE_M,
             tile_k: int = TILE_K,
             tile: int = DEFAULT_TILE) -> BlockPlan:
    """The cached plan for ``mat`` (stats live on it)."""
    plan, _runner = _runner_cache.get(mat, tile_m, tile_k, tile)
    return plan
