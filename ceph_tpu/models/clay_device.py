"""Clay layered codec as a staged TPU pipeline.

The linearized flat matrix (models/clay.py) is bit-exact but dense:
for k=8,m=4 it spends ~20x the necessary FLOPs (density ~5%). The
layered algorithm itself is MXU/VPU-friendly when expressed over whole
planes instead of per-sub-chunk host loops:

  - the pairwise coupling transforms (C<->U) are 2x2 GF-constant maps
    applied elementwise across lanes — VPU work (8 masked XORs per GF
    constant multiply, fused by XLA);
  - each plane's MDS solve is ONE small GF matrix multiply batched
    over (planes-in-level x lanes) — the same bit-sliced MXU matmul
    every other codec uses;
  - the score-level ordering of ErasureCodeClay.cc:644-709 becomes a
    short static chain (<= m+1 stages) inside one jit.

``trace_layered`` symbolically executes the host algorithm's control
flow (which depends only on (q, t, erased)) and records vectorizable
op groups; ``build_transform`` compiles them into a jitted function
``C[q*t, ssc, L] -> C'`` with recovered nodes filled in. Signatures
are cached, so encode (erased = parity nodes) compiles once per
profile. Bit-exactness vs the host plane machinery is asserted in
tests/test_clay_device.py.

RATES IN THIS FILE: every figure marked "kernel alone, not
re-measured" dates from before the device engine, the fused flush and
the benchmark existed (BASELINE.md rounds 2-6): a builder called in a
loop on device-resident arrays, never the served path. What the served
path takes, and what it measured on the chip as a whole flush program
(PR 29), is at the foot of this docstring.

``build_transform`` (kernel alone, not re-measured: 4.7 GB/s, v5e,
k=8,m=4,d=11 encode, 64 MiB batches): the score-level chain sweeps the
full [q*t, ssc, L] working set ~6x per level (permuted gathers +
masked selects); the faithful staged expression of the algorithm,
compiled per erasure signature.

Round-3 finding (``build_encode_fast``): for the ENCODE erasure
pattern (all parities erased) the score-level chain collapses to ONE
active level, so encode is exactly three stages — a 2-term pairwise
pass over the data, ONE plane-wise [m,k] MDS matmul (RS-kernel
class), and a 2-term recouple pass. The structured encoder below is
bit-exact and does ~1/20 the dense MACs (kernel alone, not
re-measured: 8.2 GB/s composed against 9.0 for the dense linearized
matrix: XLA inserts a layout copy between the gather/select producers
and the pallas custom call, and the per-slot constant-select chains do
not fuse into single passes). It is what a plain-XLA backend serves
(:func:`flush_encoder`).

Round-4 result (``build_encode_kernel``): the whole three-stage chain
inside ONE pallas kernel with the working set VMEM-resident. The key
moves: everything stays in ROW SPACE over [rows, T] lane tiles (no
layout changes exist to copy); the (node, plane) pair gathers become
0/1 ROUTING MATMULS on the MXU (<=1 one per row — exact bf16 byte
routing); per-slot GF coefficients are per-row VPU XOR chains; the
plane-wise MDS runs per plane over its contiguous z-major row group
as an [8m, 8kk] bit-matmul. ~2k MACs/byte vs the dense linearized
matrix's ~16k (the dense matrix is compute-bound at 64x the RS MAC
count). Kernel alone, not re-measured: 525 GB/s (v5e, 67 MB batches,
plateau method). Bit-exact vs the host layered oracle (both pallas-TPU
and interpret mode); the served path's encode on a pallas backend.

The single-XLA-program experiment (``build_encode_fused``; kernel
alone, not re-measured: 1.8 GB/s) is the documented negative result:
outside a kernel, the row gathers materialize and the bit-plane
expansion amplifies HBM traffic ~30x.

WHAT THE SERVED PATH TAKES (PR 29; osd/ec_util.layered_program /
layered_decode_program): ONE encode builder, :func:`flush_encoder`
(``build_encode_kernel`` on a pallas backend; a plain-XLA backend,
which cannot run a Mosaic kernel, takes ``build_encode_fast``), and
ONE decode builder, :func:`flush_decode_table` + :func:`flush_decode`
(the dense linearized transform of a signature as an int8 bit-matrix
OPERAND of one bit-sliced MXU matmul, so one compiled program per
shape bucket serves every signature). Measured on one TPU v5 lite as
WHOLE flush programs of 4 MiB objects, payload re-laid on the device,
crc rows included (my chip run, PR 29, best of 5): encode with
``build_encode_kernel`` 1.60 / 5.11 / 18.3 ms for 1 / 4 / 16 ops (2.6
to 3.7 GB/s of payload; nearly all of it is the two turns of the
layout and the crc rows, not the kernel), compiling in 22-28 s a
bucket; with ``build_encode_fast`` 3.31 / 17.4 ms for 1 / 4 ops,
compiling in 19 / 63 s (311 s for 16 ops on the described chip): the
kernel is taken. Decode, one lost shard: 0.93 / 2.86 / 7.43 ms; two:
1.20 / 3.51 / 10.7 ms; a signature's table costs 35-99 ms of host
time to build, once. Not on the served path, kept with their tests
for the ``simplicity`` issue ROADMAP.md Q3.5 queues:
``build_transform``, ``build_encode_fused``,
``build_transform_kernel`` (its tables are compiled in per signature),
``build_decode_matvec`` with ops/gf_block_sparse (a plan per
signature, compiled in).
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field

import numpy as np

from ceph_tpu.ops import bitmatrix, gf256
from ceph_tpu.utils.lru import BoundedLRU


# -- static trace ------------------------------------------------------

@dataclass
class LevelOps:
    """Vectorizable op groups for one score level (all index arrays)."""
    # phase 1: U for intact nodes
    ident: list = field(default_factory=list)      # (node, z)
    pair_a: dict = field(default_factory=dict)     # variant -> [(nxy, z, nsw, zsw)]
    # per-plane MDS decode of erased U
    planes: list = field(default_factory=list)     # [z, ...]
    # phase 2: C for erased nodes
    ident2: list = field(default_factory=list)     # (node, z)
    type_c: dict = field(default_factory=dict)     # variant -> [(nxy, z, nsw, zsw)]
    pair_b: list = field(default_factory=list)     # (nxy, z, nsw, zsw)


def trace_layered(codec, erased: frozenset[int]) -> list[LevelOps]:
    """Replay _decode_layered's control flow (ErasureCodeClay.cc:
    644-709) recording ops instead of computing bytes. ``erased`` is
    the PADDED node-id set (virtual/parity fill to m, as the host path
    builds it)."""
    q, t = codec.q, codec.t
    ssc = codec.sub_chunk_no
    zvecs = [codec.get_plane_vector(z) for z in range(ssc)]
    order = [sum(1 for i in erased if i % q == zvecs[z][i // q])
             for z in range(ssc)]
    max_score = max(order) if erased else 0
    levels = []
    for score in range(max_score + 1):
        ops = LevelOps()
        planes = [z for z in range(ssc) if order[z] == score]
        for z in planes:
            zv = zvecs[z]
            for y in range(t):
                for x in range(q):
                    node_xy = q * y + x
                    if node_xy in erased:
                        continue
                    node_sw = q * y + zv[y]
                    if zv[y] == x:
                        ops.ident.append((node_xy, z))
                    elif zv[y] < x or node_sw in erased:
                        z_sw = codec._z_sw(z, x, zv[y], y)
                        variant = 1 if zv[y] > x else 0
                        ops.pair_a.setdefault(variant, []).append(
                            (node_xy, z, node_sw, z_sw))
        ops.planes = planes
        for z in planes:
            zv = zvecs[z]
            for node_xy in sorted(erased):
                x, y = node_xy % q, node_xy // q
                node_sw = q * y + zv[y]
                if zv[y] == x:
                    ops.ident2.append((node_xy, z))
                elif node_sw not in erased:
                    z_sw = codec._z_sw(z, x, zv[y], y)
                    variant = 1 if zv[y] > x else 0
                    ops.type_c.setdefault(variant, []).append(
                        (node_xy, z, node_sw, z_sw))
                elif zv[y] < x:
                    z_sw = codec._z_sw(z, x, zv[y], y)
                    ops.pair_b.append((node_xy, z, node_sw, z_sw))
        levels.append(ops)
    return levels


# -- pft coefficient extraction ----------------------------------------

def _pft_matrix(codec, want: list[int], known_slots: list[int]
                ) -> np.ndarray:
    """2x2 (or 1x2) GF matrix of one pairwise-transform solve, probed
    from the pft codec (GF-linear)."""
    rows = []
    for basis in range(len(known_slots)):
        known = {s: np.array([1 if i == basis else 0], dtype=np.uint8)
                 for i, s in enumerate(known_slots)}
        out = codec.pft.decode_chunks(want, known)
        rows.append([int(np.asarray(out[w])[0]) for w in want])
    return np.array(rows, dtype=np.uint8).T   # [len(want), len(known)]


def pft_coefficients(codec) -> dict:
    """All coefficient matrices the trace can reference, per slot
    variant (slot order (i0,i1,i2,i3) = (1,0,3,2) when zy > x)."""
    coeffs = {}
    for variant, slots in ((0, (0, 1, 2, 3)), (1, (1, 0, 3, 2))):
        i0, i1, i2, i3 = slots
        # pair_a: (U_xy, U_sw) from (C_xy, C_sw)
        m = _pft_matrix(codec, [i2, i3], [i0, i1])
        coeffs[("a", variant)] = m                      # [2, 2]
        # type_c: C_xy from (C_sw, U_xy)
        m = _pft_matrix(codec, [i0], [i1, i2])
        coeffs[("c", variant)] = m                      # [1, 2]
    # pair_b: (C_xy, C_sw) from (U_xy, U_sw); called with zv[y] < x
    # only, so slot order is fixed at variant 0
    coeffs[("b", 0)] = _pft_matrix(codec, [0, 1], [2, 3])
    return coeffs


# -- device execution ---------------------------------------------------

def _gf_scale(x, c: int):
    """x (*) c over GF(2^8), elementwise, for a static constant c:
    XOR of up-to-8 masked constant selects (VPU work XLA fuses)."""
    import jax.numpy as jnp
    if c == 0:
        return jnp.zeros_like(x)
    if c == 1:
        return x
    y = None
    for b in range(8):
        t = int(gf256.gf_mul(c, 1 << b))
        if t == 0:
            continue
        term = jnp.where((x >> b) & 1 == 1,
                         jnp.uint8(t), jnp.uint8(0))
        y = term if y is None else y ^ term
    return y


def _combine2(m: np.ndarray, a, b):
    """[out0, out1] = m @ [a, b] over GF, m a small host matrix."""
    outs = []
    for row in m:
        acc = _gf_scale(a, int(row[0])) ^ _gf_scale(b, int(row[1]))
        outs.append(acc)
    return outs


def _varmul_tables(coef: np.ndarray) -> list[tuple[int, np.ndarray]]:
    """Bit tables for an elementwise multiply by VARYING constants:
    y[e] = coef[e] (*) x[e] = XOR_b ((x>>b)&1) * gf_mul(coef, 2^b)[e].
    Returns only the bit planes with a nonzero table."""
    out = []
    for b in range(8):
        tab = gf256.gf_mul(coef, 1 << b)
        if tab.any():
            out.append((b, tab))
    return out


def _varmul(x, tables, jnp):
    """Apply _varmul_tables to x [qt, ssc, L] (tables broadcast over
    lanes). One fused XOR chain — no scatters, no per-pair gathers."""
    y = None
    for b, tab in tables:
        t = jnp.asarray(tab[:, :, None])
        term = jnp.where((x >> b) & 1 == 1, t, jnp.uint8(0))
        y = term if y is None else y ^ term
    if y is None:
        return jnp.zeros_like(x)
    return y


def build_transform(codec, erased: frozenset[int]):
    """Jitted ``C[q*t, ssc, L] uint8 -> C'`` filling erased nodes.
    ``erased``: padded node-id set, |erased| <= m.

    Executor shape: per level, phase 1 is ONE whole-array masked pass
    ``U' = sel(mask, a1(*)C + a2(*)C[perm], U)`` (a1/a2/perm are
    static [qt, ssc] tables), the MDS solve is one bit-sliced matmul
    over (planes-in-level x lanes), and phase 2 is one more masked
    pass over C — a handful of fused HBM passes per level instead of
    per-op-group scatters."""
    import jax
    import jax.numpy as jnp

    levels = trace_layered(codec, erased)
    coeffs = pft_coefficients(codec)
    qt = codec.q * codec.t
    ssc = codec.sub_chunk_no
    intact = [i for i in range(qt) if i not in erased]
    er = sorted(erased)
    dmat = _mds_decode_matrix(codec, intact, er)
    dbmat = bitmatrix.expand_bitmatrix(dmat).astype(np.int8)

    from ceph_tpu.ops.gf_jax import _bitsliced_matvec_device

    static = []
    for ops in levels:
        # phase 1 tables: U[n,z] = a1[n,z](*)C[n,z] ^ a2[n,z](*)C[perm]
        a1 = np.zeros((qt, ssc), dtype=np.uint8)
        a2 = np.zeros((qt, ssc), dtype=np.uint8)
        pn = np.tile(np.arange(qt, dtype=np.int32)[:, None], (1, ssc))
        pz = np.tile(np.arange(ssc, dtype=np.int32)[None, :], (qt, 1))
        mask_u = np.zeros((qt, ssc), dtype=bool)
        for n, z in ops.ident:
            a1[n, z] = 1
            mask_u[n, z] = True
        for v, lst in ops.pair_a.items():
            m = coeffs[("a", v)]
            for nxy, z, nsw, zsw in lst:
                # target (nxy, z): self C + partner C
                a1[nxy, z], a2[nxy, z] = int(m[0][0]), int(m[0][1])
                pn[nxy, z], pz[nxy, z] = nsw, zsw
                mask_u[nxy, z] = True
                # target (nsw, zsw): its self is C[nsw, zsw]
                a1[nsw, zsw], a2[nsw, zsw] = int(m[1][1]), int(m[1][0])
                pn[nsw, zsw], pz[nsw, zsw] = nxy, z
                mask_u[nsw, zsw] = True
        # phase 2 tables:
        #   C[n,z] = b1(*)C[perm2] ^ b2(*)U[n,z] ^ b3(*)U[perm2]
        b1 = np.zeros((qt, ssc), dtype=np.uint8)
        b2 = np.zeros((qt, ssc), dtype=np.uint8)
        b3 = np.zeros((qt, ssc), dtype=np.uint8)
        p2n = np.tile(np.arange(qt, dtype=np.int32)[:, None],
                      (1, ssc))
        p2z = np.tile(np.arange(ssc, dtype=np.int32)[None, :],
                      (qt, 1))
        mask_c = np.zeros((qt, ssc), dtype=bool)
        for n, z in ops.ident2:
            b2[n, z] = 1
            mask_c[n, z] = True
        for v, lst in ops.type_c.items():
            m = coeffs[("c", v)]
            for nxy, z, nsw, zsw in lst:
                b1[nxy, z] = int(m[0][0])
                b2[nxy, z] = int(m[0][1])
                p2n[nxy, z], p2z[nxy, z] = nsw, zsw
                mask_c[nxy, z] = True
        mb = coeffs[("b", 0)]
        for nxy, z, nsw, zsw in ops.pair_b:
            b2[nxy, z], b3[nxy, z] = int(mb[0][0]), int(mb[0][1])
            p2n[nxy, z], p2z[nxy, z] = nsw, zsw
            mask_c[nxy, z] = True
            b2[nsw, zsw], b3[nsw, zsw] = int(mb[1][1]), int(mb[1][0])
            p2n[nsw, zsw], p2z[nsw, zsw] = nxy, z
            mask_c[nsw, zsw] = True
        static.append({
            "planes": np.asarray(ops.planes, dtype=np.int32),
            "t_a1": _varmul_tables(a1), "t_a2": _varmul_tables(a2),
            "perm": (pn, pz), "mask_u": mask_u,
            "t_b1": _varmul_tables(b1), "t_b2": _varmul_tables(b2),
            "t_b3": _varmul_tables(b3),
            "perm2": (p2n, p2z), "mask_c": mask_c,
        })

    intact_idx = jnp.asarray(np.asarray(intact, dtype=np.int32))
    er_idx = jnp.asarray(np.asarray(er, dtype=np.int32))

    @jax.jit
    def transform(c_in):
        C = c_in
        U = jnp.zeros_like(C)
        L = C.shape[-1]
        for entry in static:
            # phase 1: one masked whole-array pass
            pn, pz = entry["perm"]
            cp = C[jnp.asarray(pn), jnp.asarray(pz)]
            cand = _varmul(C, entry["t_a1"], jnp) ^ \
                _varmul(cp, entry["t_a2"], jnp)
            U = jnp.where(jnp.asarray(entry["mask_u"])[:, :, None],
                          cand, U)
            # MDS decode of erased U on this level's planes
            if len(entry["planes"]):
                planes = jnp.asarray(entry["planes"])
                x = U[intact_idx][:, planes, :].reshape(
                    len(intact), -1)
                y = _bitsliced_matvec_device(jnp.asarray(dbmat), x)
                y = y.reshape(len(er), len(entry["planes"]), L)
                U = U.at[er_idx[:, None], planes[None, :]].set(y)
            # phase 2: one masked whole-array pass
            p2n, p2z = entry["perm2"]
            cp2 = C[jnp.asarray(p2n), jnp.asarray(p2z)]
            up2 = U[jnp.asarray(p2n), jnp.asarray(p2z)]
            cand = _varmul(cp2, entry["t_b1"], jnp) ^ \
                _varmul(U, entry["t_b2"], jnp) ^ \
                _varmul(up2, entry["t_b3"], jnp)
            C = jnp.where(jnp.asarray(entry["mask_c"])[:, :, None],
                          cand, C)
        return C

    return transform


def _mds_decode_matrix(codec, intact: list, er: list) -> np.ndarray:
    """[len(er), len(intact)] matrix recovering erased-U from intact-U
    (identical per plane), probed from the scalar MDS codec."""
    probe = {i: np.zeros(len(intact), dtype=np.uint8) for i in intact}
    for idx, i in enumerate(intact):
        probe[i][idx] = 1
    sol = codec.mds.decode_chunks(er, probe)
    return np.stack([np.asarray(sol[i], dtype=np.uint8) for i in er])


def build_encode_fast(codec, tables_only: bool = False,
                      matvec_device=None):
    """Structured device ENCODE (the round-2 verdict's plane-blocked
    kernel, ErasureCodeClay.cc:644-709 coupling structure): for the
    all-parity erasure pattern the score-level chain collapses to ONE
    active level, so encode is exactly three stages —

      1. U_data = pairwise uncouple of C_data (2-term GF combos, one
         gather + two constant-table passes over the data array; the
         erased partners' C is zero by construction and drops out);
      2. U_parity = the plane-wise MDS encode — ONE [m,k] bit-sliced
         MXU matmul over (ssc x lanes), the same shape/throughput
         class as the plain RS kernel;
      3. C_parity = pairwise recouple (2-term combos reading U_parity
         and gathered C_data).

    vs the dense [m*ssc, k*ssc] signature matrix this does ~1/20 the
    MACs (the matrix is ~5% dense) and ~6 HBM passes instead of a
    compute-bound dense matmul. Returns a jitted
    ``[k, ssc, L] uint8 -> [m, ssc, L]`` (bit-exact vs the host
    layered machinery — gated in tests)."""
    import jax
    import jax.numpy as jnp

    q, t = codec.q, codec.t
    qt, ssc = q * t, codec.sub_chunk_no
    k, m = codec.k, codec.m
    erased = frozenset(codec._node_id(i) for i in range(k, k + m))
    levels = trace_layered(codec, erased)
    active = [ops for ops in levels
              if ops.ident or ops.pair_a or ops.planes]
    assert len(active) == 1 and sorted(active[0].planes) == \
        list(range(ssc)), "encode trace is not single-level"
    ops = active[0]
    coeffs = pft_coefficients(codec)
    # intact rows = data nodes (grid ids 0..k-1) PLUS the nu virtual
    # nodes (grid ids k..k+nu-1) of profiles where q does not divide
    # k+m: virtual C is zero, but virtual U mixes real data and feeds
    # the MDS solve, so they get real rows
    intact = [i for i in range(qt) if i not in erased]
    kk = len(intact)
    assert kk == k + codec.nu, (kk, k, codec.nu)
    er = sorted(erased)
    row_of = {n: idx for idx, n in enumerate(intact)}
    prow_of = {n: idx for idx, n in enumerate(er)}
    #: input embedding: padded row -> data chunk index (-1 = virtual)
    src = np.full(kk, -1, dtype=np.int32)
    for i in range(k):
        src[row_of[codec._node_id(i)]] = i

    # stage 1 tables over INTACT slots [kk, ssc]
    a1 = np.zeros((kk, ssc), dtype=np.uint8)
    a2 = np.zeros((kk, ssc), dtype=np.uint8)
    perm = np.zeros((kk, ssc), dtype=np.int32)   # flat intact-slot idx
    for n, z in ops.ident:
        a1[row_of[n], z] = 1
        perm[row_of[n], z] = row_of[n] * ssc + z
    for v, lst in ops.pair_a.items():
        mm = coeffs[("a", v)]
        for nxy, z, nsw, zsw in lst:
            r = row_of[nxy]
            a1[r, z], perm[r, z] = int(mm[0][0]), r * ssc + z
            if nsw in erased:
                # partner C is an erased node: zero by construction
                a2[r, z] = 0
            else:
                a2[r, z] = int(mm[0][1])
                perm[r, z] = row_of[nsw] * ssc + zsw
            rs = prow_of.get(nsw)
            if rs is None:
                r2 = row_of[nsw]
                a1[r2, zsw] = int(mm[1][1])
                a2[r2, zsw] = int(mm[1][0])
                perm[r2, zsw] = r * ssc + z
    dmat = _mds_decode_matrix(codec, intact, er)

    # stage 3 tables over PARITY slots [m, ssc]
    b1 = np.zeros((m, ssc), dtype=np.uint8)      # * C_data[perm_c]
    b2 = np.zeros((m, ssc), dtype=np.uint8)      # * U_par[self]
    b3 = np.zeros((m, ssc), dtype=np.uint8)      # * U_par[perm_u]
    perm_c = np.zeros((m, ssc), dtype=np.int32)
    perm_u = np.zeros((m, ssc), dtype=np.int32)
    for n, z in ops.ident2:
        b2[prow_of[n], z] = 1
    for v, lst in ops.type_c.items():
        mm = coeffs[("c", v)]
        for nxy, z, nsw, zsw in lst:
            r = prow_of[nxy]
            b1[r, z] = int(mm[0][0])
            perm_c[r, z] = row_of[nsw] * ssc + zsw
            b2[r, z] = int(mm[0][1])
    mb = coeffs[("b", 0)]
    for nxy, z, nsw, zsw in ops.pair_b:
        r, rs = prow_of[nxy], prow_of[nsw]
        b2[r, z], b3[r, z] = int(mb[0][0]), int(mb[0][1])
        perm_u[r, z] = rs * ssc + zsw
        b2[rs, zsw], b3[rs, zsw] = int(mb[1][1]), int(mb[1][0])
        perm_u[rs, zsw] = r * ssc + z

    tables = {
        "kk": kk, "ssc": ssc, "k": k, "m": m, "dmat": dmat,
        "t_a1": _varmul_tables(a1.reshape(-1, 1)),
        "t_a2": _varmul_tables(a2.reshape(-1, 1)),
        "t_b1": _varmul_tables(b1.reshape(-1, 1)),
        "t_b2": _varmul_tables(b2.reshape(-1, 1)),
        "t_b3": _varmul_tables(b3.reshape(-1, 1)),
        "perm": perm.reshape(-1), "perm_c": perm_c.reshape(-1),
        "perm_u": perm_u.reshape(-1), "src": src,
        "a1": a1.reshape(-1), "a2": a2.reshape(-1),
        "b1": b1.reshape(-1), "b2": b2.reshape(-1),
        "b3": b3.reshape(-1),
    }
    if tables_only:
        # kernel/fused builders want only the structure tables — skip
        # building the staged jit closures and device constants
        class _T:
            pass
        holder = _T()
        holder.tables = tables
        return holder
    if matvec_device is None:
        from ceph_tpu.ops import backend as backend_mod
        try:
            resolved, _ = backend_mod.resolve(codec.backend)
        except KeyError:
            resolved = "jax"
        if resolved == "pallas":
            from ceph_tpu.ops.gf_pallas import matvec_device
        else:
            from ceph_tpu.ops.gf_jax import matvec_device
    t_a1, t_a2 = tables["t_a1"], tables["t_a2"]
    t_b1, t_b2, t_b3 = (tables["t_b1"], tables["t_b2"],
                        tables["t_b3"])
    perm_f = jnp.asarray(perm.reshape(-1))
    perm_cf = jnp.asarray(perm_c.reshape(-1))
    perm_uf = jnp.asarray(perm_u.reshape(-1))
    src_j = jnp.asarray(np.maximum(src, 0))
    virt = jnp.asarray((src < 0)[:, None, None])

    # the three stages live in two jitted pieces around the backend
    # matvec (itself jitted/bucketed); XLA fuses the elementwise
    # chains on each side
    @jax.jit
    def stage1(c_data):
        L = c_data.shape[-1]
        # embed the k data chunks into the kk intact rows (virtual
        # node rows are zero)
        padded = jnp.where(virt, jnp.uint8(0), c_data[src_j])
        flat = padded.reshape(kk * ssc, L)
        u_d = _varmul(flat[:, None, :], t_a1, jnp) ^ \
            _varmul(flat[perm_f][:, None, :], t_a2, jnp)
        return padded, u_d.reshape(kk, ssc * L)

    @jax.jit
    def stage3(padded, u_par):
        L = padded.shape[-1]
        flat_c = padded.reshape(kk * ssc, L)
        flat_u = u_par.reshape(m * ssc, L)
        out = _varmul(flat_c[perm_cf][:, None, :], t_b1, jnp) ^ \
            _varmul(flat_u[:, None, :], t_b2, jnp) ^ \
            _varmul(flat_u[perm_uf][:, None, :], t_b3, jnp)
        return out.reshape(m, ssc, L)

    def encode_fast(c_data):
        padded, u_d = stage1(c_data)
        u_p = matvec_device(dmat, u_d)       # [m, ssc*L], trace-safe
        u_p = u_p.reshape(m, ssc, padded.shape[-1])
        return stage3(padded, u_p)

    encode_fast.tables = tables
    return encode_fast


def build_encode_fused(codec):
    """Round-4: the three structured-encode stages as ONE XLA program
    (no custom-call boundaries, no per-stage jit seams). The round-3
    composition ran at 8.2 GB/s (kernel alone, not re-measured)
    because each stage was its own jitted
    piece: XLA inserted layout copies into the pallas custom call and
    could not fuse the select chains across dispatch boundaries. Here
    the pairwise uncouple (gather + xor chains), the plane-wise MDS
    bit-sliced MXU matmul, and the recouple live in a single jit —
    XLA fuses the elementwise chains into the matmul's operand and
    result producers, and the working set streams through one fused
    program. Same tables, bit-exact with the host layered oracle.

    Returns jitted ``[k, ssc, L] uint8 -> [m, ssc, L]`` with
    L pow2-bucketed by the wrapper (bounded compiles, like every
    daemon-facing device entry)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ops import bitmatrix

    fast = build_encode_fast(codec, tables_only=True)
    tb = fast.tables
    kk, ssc, k, m = tb["kk"], tb["ssc"], tb["k"], tb["m"]
    bmat = jnp.asarray(
        bitmatrix.expand_bitmatrix(tb["dmat"]).astype(np.int8))
    t_a1, t_a2 = tb["t_a1"], tb["t_a2"]
    t_b1, t_b2, t_b3 = tb["t_b1"], tb["t_b2"], tb["t_b3"]
    perm_f = jnp.asarray(tb["perm"])
    perm_cf = jnp.asarray(tb["perm_c"])
    perm_uf = jnp.asarray(tb["perm_u"])
    src_j = jnp.asarray(np.maximum(tb["src"], 0))
    virt = jnp.asarray((tb["src"] < 0)[:, None, None])
    shifts = jnp.arange(8, dtype=jnp.uint8)

    @jax.jit
    def fused(c_data):
        L = c_data.shape[-1]
        padded = jnp.where(virt, jnp.uint8(0), c_data[src_j])
        flat = padded.reshape(kk * ssc, L)
        u_d = _varmul(flat[:, None, :], t_a1, jnp) ^ \
            _varmul(flat[perm_f][:, None, :], t_a2, jnp)
        u_d = u_d.reshape(kk, ssc * L)
        # plane-wise MDS encode, bit-sliced onto the MXU, inline
        dbits = ((u_d[:, None, :] >> shifts[None, :, None]) & 1
                 ).astype(jnp.int8).reshape(8 * kk, ssc * L)
        acc = jax.lax.dot_general(
            bmat, dbits, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        pbits = (acc & 1).astype(jnp.uint8).reshape(m, 8, ssc * L)
        weights = (jnp.uint8(1) << shifts)[None, :, None]
        u_p = (pbits * weights).sum(axis=1, dtype=jnp.uint32
                                    ).astype(jnp.uint8)
        flat_u = u_p.reshape(m * ssc, L)
        out = _varmul(flat[perm_cf][:, None, :], t_b1, jnp) ^ \
            _varmul(flat_u[:, None, :], t_b2, jnp) ^ \
            _varmul(flat_u[perm_uf][:, None, :], t_b3, jnp)
        return out.reshape(m, ssc, L)

    def encode(c_data):
        c_data = jnp.asarray(c_data, dtype=jnp.uint8)
        L = c_data.shape[-1]
        lb = 1 << 10
        while lb < L:
            lb <<= 1
        if lb != L:
            c_data = jnp.pad(c_data, ((0, 0), (0, 0), (0, lb - L)))
        out = fused(c_data)
        return out[:, :, :L] if lb != L else out

    encode.tables = tb
    return encode


def build_encode_kernel(codec, tile: int = 512):
    """Round-4: the WHOLE structured encode chain in ONE Pallas
    kernel with a VMEM-resident working set (the round-3 deferral's
    prescription). Everything runs in ROW SPACE over [rows, T] lane
    tiles, so no layout copies ever occur:

    - the pairwise couplings' (node, plane) gathers are ROW
      permutations of the tile — executed as MXU matmuls with 0/1
      routing matrices (<=1 one per row: bf16 products and f32 sums
      are exact byte routing);
    - the per-slot GF coefficients are per-row constant XOR chains on
      the VPU (the _varmul decomposition, tables as [rows, 1] refs);
    - the plane-wise MDS encode runs per plane z over the contiguous
      [z*kk, (z+1)*kk) row group: unpack bits -> one [8m, 8kk]
      bit-matmul on the MXU -> weighted-sum repack, all in VMEM.

    ~2k MACs/byte total vs the dense linearized matrix's ~16k (dense
    is compute-bound at 64x the RS MAC count). Bit-exact vs the host
    layered oracle. As the served flush program's encode (PR 29, my
    chip run): see the module docstring.

    Returns ``[k, ssc, L] uint8 -> [m, ssc, L]`` with L pow2-bucketed.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ceph_tpu.ops import bitmatrix
    from ceph_tpu.ops.gf_pallas import _permute_bitmatrix

    fast = build_encode_fast(codec, tables_only=True)
    tb = fast.tables
    kk, ssc, k, m = tb["kk"], tb["ssc"], tb["k"], tb["m"]
    src = tb["src"]
    R_in, R_ud, R_out = k * ssc, kk * ssc, m * ssc

    def _col_of(intact_flat: int) -> int | None:
        j2, z = divmod(int(intact_flat), ssc)
        i = int(src[j2])
        return None if i < 0 else i * ssc + z

    # routing matrices (0/1, <=1 per row) + z-major coefficient tables
    p_self = np.zeros((R_ud, R_in), dtype=np.float32)
    p_a = np.zeros((R_ud, R_in), dtype=np.float32)
    a1z = np.zeros((R_ud, 1), dtype=np.uint8)
    a2z = np.zeros((R_ud, 1), dtype=np.uint8)
    a1, a2 = tb["a1"], tb["a2"]
    b1, b2, b3 = tb["b1"], tb["b2"], tb["b3"]
    perm, perm_c, perm_u = tb["perm"], tb["perm_c"], tb["perm_u"]
    for j2 in range(kk):
        for z in range(ssc):
            r = z * kk + j2                  # z-major u_d row
            flat = j2 * ssc + z              # node-major intact idx
            col = _col_of(flat)
            if col is not None:
                p_self[r, col] = 1.0
            a1z[r, 0] = a1[flat]
            colp = _col_of(perm[flat])
            if colp is not None and a2[flat]:
                p_a[r, colp] = 1.0
            a2z[r, 0] = a2[flat]
    p_c = np.zeros((R_out, R_in), dtype=np.float32)
    p_su = np.zeros((R_out, R_out), dtype=np.float32)
    p_u = np.zeros((R_out, R_out), dtype=np.float32)
    b1c = np.zeros((R_out, 1), dtype=np.uint8)
    b2c = np.zeros((R_out, 1), dtype=np.uint8)
    b3c = np.zeros((R_out, 1), dtype=np.uint8)
    for i in range(m):
        for z in range(ssc):
            r = i * ssc + z                  # node-major parity row
            b1c[r, 0], b2c[r, 0], b3c[r, 0] = (b1[r], b2[r], b3[r])
            colc = _col_of(perm_c[r])
            if colc is not None and b1[r]:
                p_c[r, colc] = 1.0
            p_su[r, z * m + i] = 1.0         # u_p rows are z-major
            i2, z2 = divmod(int(perm_u[r]), ssc)
            p_u[r, z2 * m + i2] = 1.0
    bmat = _permute_bitmatrix(
        np.asarray(tb["dmat"], dtype=np.uint8)).astype(np.float32)

    def _vartabs(coef: np.ndarray):
        """(bits tuple, stacked [P, rows] table array) for a varying
        constant multiply — stacked so the planes ride ONE kernel
        input ref instead of captured constants."""
        tabs = _varmul_tables(coef.reshape(-1, 1))
        if not tabs:
            return (), np.zeros((coef.size, 1), dtype=np.int32)
        bits = tuple(b for b, _ in tabs)
        # [rows, P] int32: slicing one plane keeps both dims (Mosaic
        # cannot insert a minor dim on sub-32-bit types) and the
        # whole select/xor chain runs in 32-bit lanes
        stacked = np.stack([t.reshape(-1) for _, t in tabs],
                           axis=1).astype(np.int32)
        return bits, stacked

    bits_a1, tab_a1 = _vartabs(a1z)
    bits_a2, tab_a2 = _vartabs(a2z)
    bits_b1, tab_b1 = _vartabs(b1c)
    bits_b2, tab_b2 = _vartabs(b2c)
    bits_b3, tab_b3 = _vartabs(b3c)

    def _vm(x, tab_ref, bits):
        """x int32 [rows, T]; tab_ref [rows, P] int32."""
        y = None
        for pi, b in enumerate(bits):
            t = tab_ref[:, pi:pi + 1]         # [rows, 1] int32
            term = jnp.where((x >> b) & 1 == 1, t, 0)
            y = term if y is None else y ^ term
        return jnp.zeros_like(x) if y is None else y

    def kernel(c_ref, ps_ref, pa_ref, pc_ref, psu_ref, pu_ref,
               bm_ref, ta1_ref, ta2_ref, tb1_ref, tb2_ref, tb3_ref,
               out_ref):
        c = c_ref[:]                          # [R_in, T] uint8
        # Mosaic has no direct u8<->bf16 casts: hop through int32;
        # every intermediate stays 32-bit until the final store
        cf = c.astype(jnp.int32).astype(jnp.bfloat16)
        route = lambda p_ref: jax.lax.dot_general(
            p_ref[:].astype(jnp.bfloat16), cf,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        c_self = route(ps_ref)                # [R_ud, T] int32
        c_pair = route(pa_ref)
        u_d = _vm(c_self, ta1_ref, bits_a1) ^ \
            _vm(c_pair, ta2_ref, bits_a2)
        # plane-wise MDS over contiguous z-major row groups
        ups = []
        w = jnp.left_shift(
            1, jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0))
        for z in range(ssc):
            grp = u_d[z * kk:(z + 1) * kk]    # int32
            parts = []
            for cbit in range(8):
                parts.append((grp >> cbit) & 1)
            bits = jnp.concatenate(parts, axis=0)   # [8kk, T]
            acc = jax.lax.dot_general(
                bm_ref[:].astype(jnp.bfloat16),
                bits.astype(jnp.bfloat16),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            bbits = acc.astype(jnp.int32) & 1       # [8m, T]
            rows = []
            for i in range(m):
                bb = bbits[8 * i:8 * i + 8]
                rows.append(jnp.sum(bb * w, axis=0, keepdims=True))
            ups.append(jnp.concatenate(rows, axis=0))
        u_p = jnp.concatenate(ups, axis=0)    # int32 rows
        upf = u_p.astype(jnp.bfloat16)
        routeu = lambda p_ref: jax.lax.dot_general(
            p_ref[:].astype(jnp.bfloat16), upf,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        cpart = jax.lax.dot_general(
            pc_ref[:].astype(jnp.bfloat16), cf,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        out = _vm(cpart, tb1_ref, bits_b1) ^ \
            _vm(routeu(psu_ref), tb2_ref, bits_b2) ^ \
            _vm(routeu(pu_ref), tb3_ref, bits_b3)
        out_ref[:] = out.astype(jnp.uint8)

    consts = [jnp.asarray(p_self), jnp.asarray(p_a),
              jnp.asarray(p_c), jnp.asarray(p_su), jnp.asarray(p_u),
              jnp.asarray(bmat), jnp.asarray(tab_a1),
              jnp.asarray(tab_a2), jnp.asarray(tab_b1),
              jnp.asarray(tab_b2), jnp.asarray(tab_b3)]

    @functools.partial(jax.jit, static_argnames=("L",))
    def run_padded(cflat, L):
        grid = (L // tile,)
        whole = lambda shape: pl.BlockSpec(
            shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((R_in, tile), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                whole(p_self.shape), whole(p_a.shape),
                whole(p_c.shape), whole(p_su.shape),
                whole(p_u.shape), whole(bmat.shape),
                whole(tab_a1.shape), whole(tab_a2.shape),
                whole(tab_b1.shape), whole(tab_b2.shape),
                whole(tab_b3.shape),
            ],
            out_specs=pl.BlockSpec((R_out, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((R_out, L), jnp.uint8),
            interpret=jax.default_backend() == "cpu",
            name="clay_encode",
        )(cflat, *consts)

    def encode(c_data):
        c_data = jnp.asarray(c_data, dtype=jnp.uint8)
        L = c_data.shape[-1]
        lb = tile
        while lb < L:
            lb <<= 1
        flat = c_data.reshape(R_in, L)
        if lb != L:
            flat = jnp.pad(flat, ((0, 0), (0, lb - L)))
        out = run_padded(flat, lb)
        if lb != L:
            out = out[:, :L]
        return out.reshape(m, ssc, L)

    encode.tables = tb
    return encode


def build_decode_tables(codec, erased: frozenset[int]) -> dict:
    """Global (level-independent) slot tables + per-level masks for
    the layered DECODE chain (decode_layered,
    src/erasure-code/clay/ErasureCodeClay.cc:644-709).

    Key round-5 observation: the per-slot coefficient and partner
    assignments of build_transform's per-level tables are GEOMETRIC —
    fixed by (slot, erased signature), independent of the score level
    (the pairing (n,z)<->(nsw,zsw) is an involution; each slot is
    consistently the low or the high member of its pair, and the
    erased-set membership that picks the coefficient variant is
    static). Only WHICH slots update varies by level. So one set of
    global tables + one mask column per level expresses the whole
    multi-level chain — which is what lets the decode kernel unroll
    the levels inside a single pallas program with shared routing
    matrices. Overlap consistency is asserted while merging.
    """
    levels = trace_layered(codec, erased)
    coeffs = pft_coefficients(codec)
    qt = codec.q * codec.t
    ssc = codec.sub_chunk_no

    a1 = np.zeros((qt, ssc), dtype=np.uint8)
    a2 = np.zeros((qt, ssc), dtype=np.uint8)
    pn = np.tile(np.arange(qt, dtype=np.int32)[:, None], (1, ssc))
    pz = np.tile(np.arange(ssc, dtype=np.int32)[None, :], (qt, 1))
    b1 = np.zeros((qt, ssc), dtype=np.uint8)
    b2 = np.zeros((qt, ssc), dtype=np.uint8)
    b3 = np.zeros((qt, ssc), dtype=np.uint8)
    p2n = np.tile(np.arange(qt, dtype=np.int32)[:, None], (1, ssc))
    p2z = np.tile(np.arange(ssc, dtype=np.int32)[None, :], (qt, 1))
    seen_u = np.zeros((qt, ssc), dtype=bool)
    seen_c = np.zeros((qt, ssc), dtype=bool)
    masks_u, masks_c, level_planes = [], [], []

    def put_u(n, z, v1, v2, tn, tz):
        if seen_u[n, z]:
            assert (a1[n, z], a2[n, z], pn[n, z], pz[n, z]) == \
                (v1, v2, tn, tz), "level-dependent U slot"
        seen_u[n, z] = True
        a1[n, z], a2[n, z] = v1, v2
        pn[n, z], pz[n, z] = tn, tz

    def put_c(n, z, v1, v2, v3, tn, tz):
        if seen_c[n, z]:
            assert (b1[n, z], b2[n, z], b3[n, z], p2n[n, z],
                    p2z[n, z]) == (v1, v2, v3, tn, tz), \
                "level-dependent C slot"
        seen_c[n, z] = True
        b1[n, z], b2[n, z], b3[n, z] = v1, v2, v3
        p2n[n, z], p2z[n, z] = tn, tz

    for ops in levels:
        mu = np.zeros((qt, ssc), dtype=bool)
        mc = np.zeros((qt, ssc), dtype=bool)
        for n, z in ops.ident:
            put_u(n, z, 1, 0, n, z)
            mu[n, z] = True
        for v, lst in ops.pair_a.items():
            mm = coeffs[("a", v)]
            for nxy, z, nsw, zsw in lst:
                put_u(nxy, z, int(mm[0][0]), int(mm[0][1]), nsw, zsw)
                mu[nxy, z] = True
                put_u(nsw, zsw, int(mm[1][1]), int(mm[1][0]), nxy, z)
                mu[nsw, zsw] = True
        for n, z in ops.ident2:
            put_c(n, z, 0, 1, 0, n, z)
            mc[n, z] = True
        for v, lst in ops.type_c.items():
            mm = coeffs[("c", v)]
            for nxy, z, nsw, zsw in lst:
                put_c(nxy, z, int(mm[0][0]), int(mm[0][1]), 0,
                      nsw, zsw)
                mc[nxy, z] = True
        mb = coeffs[("b", 0)]
        for nxy, z, nsw, zsw in ops.pair_b:
            put_c(nxy, z, 0, int(mb[0][0]), int(mb[0][1]), nsw, zsw)
            mc[nxy, z] = True
            put_c(nsw, zsw, 0, int(mb[1][1]), int(mb[1][0]), nxy, z)
            mc[nsw, zsw] = True
        masks_u.append(mu)
        masks_c.append(mc)
        level_planes.append(list(ops.planes))
    return {
        "a1": a1, "a2": a2, "pn": pn, "pz": pz,
        "b1": b1, "b2": b2, "b3": b3, "p2n": p2n, "p2z": p2z,
        "masks_u": masks_u, "masks_c": masks_c,
        "planes": level_planes,
    }


def build_transform_kernel(codec, erased: frozenset[int],
                           tile: int = 256):
    """Round-5: the WHOLE multi-level layered decode chain in ONE
    Pallas kernel — the decode counterpart of ``build_encode_kernel``
    (matching decode_layered, ErasureCodeClay.cc:644-709). The dense
    linearized decode matrix is COMPUTE-bound at ~5% density (14.4
    GB/s for decode-2, BASELINE.md: kernel alone, not re-measured);
    this runs the sparse structure directly (NOT the served path's
    decode: its tables are compiled in per signature):

    - state lives Z-MAJOR, each plane's node group PADDED to
      P = ceil(qt/8)*8 rows (row z*P + n): every per-plane MDS slice
      is then a CONTIGUOUS, sublane-ALIGNED static slice of a VMEM
      scratch ref — scratch + aligned in-place stores are what let
      Mosaic REUSE buffers across the ssc-plane unroll (the
      value-SSA formulation stacked every unrolled plane's temps:
      20.7 MiB scoped vmem vs the 16 MiB budget, chip-measured);
    - the node-major -> z-major embedding runs outside as one XLA
      transpose (its in-kernel [R, R] routing matrix was the largest
      single constant);
    - the global pairwise-coupling tables of build_decode_tables make
      the per-level work a shared routing matmul (S_pair) + per-row
      VPU coefficient chains + a per-level mask select — levels
      unroll statically inside the kernel;
    - each level's plane-wise MDS decode is one [8e, 8P] bit-matmul
      per plane group (zero columns at erased/pad nodes), recovered
      rows stored 8-aligned into a rec scratch and scattered back by
      one small routing matmul;
    - phase 2 computes candidates only for the e*ssc ERASED rows
      (C writes always target erased slots) — small matmuls.

    All routing constants are bf16 (0/1 and byte values are exact).
    Returns ``[qt, ssc, L] uint8 (erased rows zero) ->
    [e, ssc, L] uint8`` recovered C for sorted(erased).
    ``erased`` must be the PADDED node-id set (|erased| == m the way
    _decode_layered pads it).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ceph_tpu.ops.gf_pallas import _permute_bitmatrix

    tb = build_decode_tables(codec, erased)
    q, t = codec.q, codec.t
    qt, ssc = q * t, codec.sub_chunk_no
    P = ((qt + 7) // 8) * 8            # plane group rows, 8-aligned
    Rp = ssc * P                       # padded z-major state rows
    er = sorted(erased)
    e = len(er)
    E8 = ((e + 7) // 8) * 8            # rec rows per plane, 8-aligned
    intact = [i for i in range(qt) if i not in erased]
    n_levels = len(tb["masks_u"])

    # MDS decode matrix widened to P columns (zeros at erased + pad)
    dmat_small = _mds_decode_matrix(codec, intact, er)   # [e, kk]
    dmat_full = np.zeros((e, P), dtype=np.uint8)
    for col, n in enumerate(intact):
        dmat_full[:, n] = dmat_small[:, col]
    dbmat = _permute_bitmatrix(dmat_full)                # [8e, 8P]

    def zr(n, z):                      # padded z-major state row
        return z * P + n

    a1, a2, pn, pz = tb["a1"], tb["a2"], tb["pn"], tb["pz"]
    s_pair = np.zeros((Rp, Rp), dtype=np.float32)
    a1z = np.zeros((Rp, 1), dtype=np.uint8)
    a2z = np.zeros((Rp, 1), dtype=np.uint8)
    for n in range(qt):
        for z in range(ssc):
            r = zr(n, z)
            a1z[r, 0], a2z[r, 0] = a1[n, z], a2[n, z]
            if a2[n, z]:
                s_pair[r, zr(pn[n, z], pz[n, z])] = 1.0
    # recovered-U scatter: rec row z*E8 + j -> U row zr(er[j], z)
    s_back = np.zeros((Rp, ssc * E8), dtype=np.float32)
    for z in range(ssc):
        for j in range(e):
            s_back[zr(er[j], z), z * E8 + j] = 1.0
    # phase-2 tables over the e*ssc erased rows (plane-major rc order,
    # padded to E8 rows per plane so the scatter matrix is shared)
    b1, b2, b3 = tb["b1"], tb["b2"], tb["b3"]
    p2n, p2z = tb["p2n"], tb["p2z"]
    Rrc = ssc * E8
    p2c = np.zeros((Rrc, Rp), dtype=np.float32)
    s2u = np.zeros((Rrc, Rp), dtype=np.float32)
    p2u = np.zeros((Rrc, Rp), dtype=np.float32)
    b1c = np.zeros((Rrc, 1), dtype=np.uint8)
    b2c = np.zeros((Rrc, 1), dtype=np.uint8)
    b3c = np.zeros((Rrc, 1), dtype=np.uint8)
    for z in range(ssc):
        for j, n in enumerate(er):
            r = z * E8 + j
            b1c[r, 0], b2c[r, 0], b3c[r, 0] = \
                b1[n, z], b2[n, z], b3[n, z]
            if b1[n, z]:
                p2c[r, zr(p2n[n, z], p2z[n, z])] = 1.0
            if b2[n, z]:
                s2u[r, zr(n, z)] = 1.0
            if b3[n, z]:
                p2u[r, zr(p2n[n, z], p2z[n, z])] = 1.0
    # output extraction: out row j*ssc + z (node-major) <- state row
    R_out = e * ssc
    s_out = np.zeros((R_out, Rp), dtype=np.float32)
    for j, n in enumerate(er):
        for z in range(ssc):
            s_out[j * ssc + z, zr(n, z)] = 1.0
    # per-level masks as stacked int32 columns
    mu_cols = np.zeros((Rp, n_levels), dtype=np.int32)
    mmds_cols = np.zeros((Rp, n_levels), dtype=np.int32)
    mc_cols = np.zeros((Rp, n_levels), dtype=np.int32)
    for li in range(n_levels):
        mu, mc = tb["masks_u"][li], tb["masks_c"][li]
        for n in range(qt):
            for z in range(ssc):
                if mu[n, z]:
                    mu_cols[zr(n, z), li] = 1
                if mc[n, z]:
                    mc_cols[zr(n, z), li] = 1
        for z in tb["planes"][li]:
            for n in er:
                mmds_cols[zr(n, z), li] = 1

    bits_a1, tab_a1 = _vartabs_of(a1z)
    bits_a2, tab_a2 = _vartabs_of(a2z)
    bits_b1, tab_b1 = _vartabs_of(b1c)
    bits_b2, tab_b2 = _vartabs_of(b2c)
    bits_b3, tab_b3 = _vartabs_of(b3c)

    def _vm(x, tab_ref, bits):
        y = None
        for pi, b in enumerate(bits):
            tt = tab_ref[:, pi:pi + 1]
            term = jnp.where((x >> b) & 1 == 1, tt, 0)
            y = term if y is None else y ^ term
        return jnp.zeros_like(x) if y is None else y

    def kernel(c_ref, pair_ref, back_ref, p2c_ref, s2u_ref,
               p2u_ref, sout_ref, bm_ref, mu_ref, mmds_ref, mc_ref,
               ta1_ref, ta2_ref, tb1_ref, tb2_ref, tb3_ref, out_ref,
               cz_ref, u_ref, rec_ref):
        route = lambda p_ref, xf: jax.lax.dot_general(
            p_ref[:], xf,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        cz_ref[:] = c_ref[:].astype(jnp.int32)   # z-major state
        u_ref[:] = jnp.zeros_like(u_ref)
        w = jnp.left_shift(
            1, jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0))
        for li in range(n_levels):
            cz = cz_ref[:]
            czf = cz.astype(jnp.bfloat16)
            cand_u = _vm(cz, ta1_ref, bits_a1) ^ \
                _vm(route(pair_ref, czf), ta2_ref, bits_a2)
            u_ref[:] = jnp.where(mu_ref[:, li:li + 1] == 1, cand_u,
                                 u_ref[:])
            # plane-wise MDS over aligned scratch slices: every
            # iteration reads/writes fixed scratch rows, so the
            # unroll reuses one iteration's buffers
            for z in range(ssc):
                grp = u_ref[z * P:(z + 1) * P, :]
                parts = [(grp >> cbit) & 1 for cbit in range(8)]
                bits = jnp.concatenate(parts, axis=0)   # [8P, T]
                acc = jax.lax.dot_general(
                    bm_ref[:], bits.astype(jnp.bfloat16),
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                bbits = acc.astype(jnp.int32) & 1       # [8e, T]
                rows = [jnp.sum(bbits[8 * j:8 * j + 8] * w, axis=0,
                                keepdims=True) for j in range(e)]
                rows.append(jnp.zeros((E8 - e, grp.shape[-1]),
                                      jnp.int32))
                rec_ref[z * E8:(z + 1) * E8, :] = \
                    jnp.concatenate(rows, axis=0)
            u_ref[:] = jnp.where(
                mmds_ref[:, li:li + 1] == 1,
                route(back_ref, rec_ref[:].astype(jnp.bfloat16)),
                u_ref[:])
            # phase 2: candidates for the erased rows only
            uf = u_ref[:].astype(jnp.bfloat16)
            czf = cz_ref[:].astype(jnp.bfloat16)
            cand_c = _vm(route(p2c_ref, czf), tb1_ref, bits_b1) ^ \
                _vm(route(s2u_ref, uf), tb2_ref, bits_b2) ^ \
                _vm(route(p2u_ref, uf), tb3_ref, bits_b3)
            cz_ref[:] = jnp.where(
                mc_ref[:, li:li + 1] == 1,
                route(back_ref, cand_c.astype(jnp.bfloat16)),
                cz_ref[:])
        out = route(sout_ref, cz_ref[:].astype(jnp.bfloat16))
        out_ref[:] = out.astype(jnp.uint8)

    bf = lambda m2: jnp.asarray(m2, dtype=jnp.bfloat16)
    consts = [bf(s_pair), bf(s_back), bf(p2c),
              bf(s2u), bf(p2u), bf(s_out),
              bf(dbmat), jnp.asarray(mu_cols),
              jnp.asarray(mmds_cols), jnp.asarray(mc_cols),
              jnp.asarray(tab_a1), jnp.asarray(tab_a2),
              jnp.asarray(tab_b1), jnp.asarray(tab_b2),
              jnp.asarray(tab_b3)]
    const_shapes = [s_pair.shape, s_back.shape,
                    p2c.shape, s2u.shape, p2u.shape, s_out.shape,
                    dbmat.shape, mu_cols.shape, mmds_cols.shape,
                    mc_cols.shape, tab_a1.shape, tab_a2.shape,
                    tab_b1.shape, tab_b2.shape, tab_b3.shape]

    @functools.partial(jax.jit, static_argnames=("L",))
    def run_padded(cflat, L):
        grid = (L // tile,)
        whole = lambda shape: pl.BlockSpec(
            shape, lambda i: tuple(0 for _ in shape),
            memory_space=pltpu.VMEM)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((Rp, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM)] +
                     [whole(s) for s in const_shapes],
            out_specs=pl.BlockSpec((R_out, tile), lambda i: (0, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((R_out, L), jnp.uint8),
            scratch_shapes=[
                pltpu.VMEM((Rp, tile), jnp.int32),      # cz
                pltpu.VMEM((Rp, tile), jnp.int32),      # u
                pltpu.VMEM((ssc * E8, tile), jnp.int32),  # rec
            ],
            compiler_params=pltpu.CompilerParams(
                # the default scoped-vmem budget (16 MiB) is below
                # this kernel's resident set (multi-level unroll +
                # ~8 MiB of routing constants); raise toward the
                # physical VMEM so Mosaic stops refusing the fit
                vmem_limit_bytes=100 * 1024 * 1024,
            ),
            interpret=jax.default_backend() == "cpu",
        )(cflat, *consts)

    def transform(c_full):
        c_full = jnp.asarray(c_full, dtype=jnp.uint8)
        L = c_full.shape[-1]
        lb = tile
        while lb < L:
            lb <<= 1
        # z-major embedding + P-row plane-group padding happen HERE
        # as one XLA transpose+pad (one extra HBM pass) instead of an
        # in-kernel [R, R] routing matmul
        flat = jnp.pad(c_full.transpose(1, 0, 2),
                       ((0, 0), (0, P - qt), (0, 0))).reshape(Rp, L)
        if lb != L:
            flat = jnp.pad(flat, ((0, 0), (0, lb - L)))
        out = run_padded(flat, lb)
        if lb != L:
            out = out[:, :L]
        return out.reshape(e, ssc, L)

    transform.erased = er
    return transform


def _vartabs_of(coef: np.ndarray):
    """(bits tuple, stacked [rows, P] int32 table) — the shared
    varying-constant-multiply decomposition (see build_encode_kernel's
    _vartabs)."""
    tabs = _varmul_tables(coef.reshape(-1, 1))
    if not tabs:
        return (), np.zeros((coef.size, 1), dtype=np.int32)
    bits = tuple(b for b, _ in tabs)
    stacked = np.stack([t.reshape(-1) for _, t in tabs],
                       axis=1).astype(np.int32)
    return bits, stacked


def build_decode_matvec(codec, mat: np.ndarray, label: str = "decode"):
    """Round-6: pick block-sparse vs dense for a linearized signature
    matrix, BY MEASUREMENT on the device (the r5 verdict's
    prescription: a structured path becomes the default only when it
    measurably beats the dense path on-device; dense stays the
    automatic fallback).

    The sparse candidate is the gather-of-blocks kernel
    (ops/gf_block_sparse): the decode-2 matrix is ~31% occupied at
    [16, 8] plane-block granularity after greedy row clustering — a
    3.3x MXU cost cut over the dense [128, 640] sweep (encode matrix
    5.3x). The plan's static cost model gates obviously-dense
    matrices; when it predicts a win, both paths run a short
    best-of-N sample on the chip and the faster one is kept.

    ``CEPH_TPU_CLAY_SPARSE``: ``never``/``0`` forces dense,
    ``always``/``1`` forces sparse (tests exercise the kernel in
    interpret mode this way), default measures (TPU only — interpret
    mode has no meaningful timing, so CPU stays dense).

    Returns ``fn(x [k, N] uint8) -> np [m, N] uint8`` with
    ``fn.path`` in {"sparse", "dense"} and ``fn.measured`` carrying
    the calibration numbers for bench/BASELINE reporting.
    """
    import os
    import time
    import zlib

    import jax

    from ceph_tpu.ops import gf_block_sparse, gf_jax
    from ceph_tpu.utils.device_telemetry import telemetry

    mat = np.asarray(mat, dtype=np.uint8)
    sig = (f"[{mat.shape[0]}x{mat.shape[1]}]"
           f"#{zlib.crc32(mat.tobytes()):08x}")

    def dense_fn(x):
        return np.asarray(jax.device_get(gf_jax.matvec_device(mat, x)))

    def sparse_fn(x):
        return np.asarray(jax.device_get(
            gf_block_sparse.matvec_device(mat, x)))

    def done(fn, path, measured=None):
        fn.path = path
        fn.measured = measured or {}
        if measured:
            # every decided outcome lands in telemetry, forced/skipped
            # ones included — BENCH rounds carry their own explanation
            telemetry().note_calibration(label, sig, path, measured)
        return fn

    mode = os.environ.get("CEPH_TPU_CLAY_SPARSE", "auto").lower()
    if mode in ("0", "never", "off"):
        return done(dense_fn, "dense")
    if mode in ("1", "always", "force"):
        return done(sparse_fn, "sparse")
    plan = gf_block_sparse.plan_blocks(mat)
    if not plan.worthwhile or jax.default_backend() != "tpu":
        return done(dense_fn, "dense",
                    {"cost_frac": plan.cost_frac, "skipped": True})

    import jax.numpy as jnp
    sample = jnp.zeros((mat.shape[1], 1 << 15), jnp.uint8)

    def best_of(fn, reps: int = 3) -> float:
        fn(sample)                       # warm / compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(sample)
            best = min(best, time.perf_counter() - t0)
        return best

    try:
        t_dense = best_of(dense_fn)
        t_sparse = best_of(sparse_fn)
    except Exception:
        # a sparse-path fault must never take decode down: dense is
        # the always-working fallback
        return done(dense_fn, "dense", {"calibration_failed": True})
    measured = {"cost_frac": round(plan.cost_frac, 4),
                "dense_s": round(t_dense, 6),
                "sparse_s": round(t_sparse, 6),
                "label": label}
    if t_sparse < t_dense:
        return done(sparse_fn, "sparse", measured)
    return done(dense_fn, "dense", measured)


# -- the served path: ONE encode builder, ONE decode builder -----------
#
# osd/ec_util's layered flush programs take these two and nothing
# selects between alternatives at run time. A flush arrives re-laid to
# plane-major lanes ([node, plane, S stripes * sub-chunk bytes]): every
# stripe of every op of the flush is one more run of lanes.

_served_lock = threading.Lock()
#: codec.flush_key() -> the trace-safe layered encode of that profile
_served_encoders: dict = {}
#: (flush_key, present, want) -> the signature's bit matrix on the
#: device; bounded like the codec's own linearized-transform LRU (the
#: ISA decode-table cache's role). C(12, <=4) signatures exist; a
#: degraded pool of 64 PGs meets at most 64 of them
_served_tables: BoundedLRU = BoundedLRU(256)

#: lanes per step of the decode matmul: bounds the bit-plane
#: expansion XLA materializes (8x the block, int8)
DECODE_LANE_BLOCK = 1 << 14


def flush_encoder(codec):
    """The served path's layered encode of ``codec``'s profile:
    ``planes [k, ssc, L] uint8 -> parity planes [m, ssc, L]``, safe to
    call inside an outer jit (ec_util.layered_program): pairwise
    uncouple, the ssc plane-wise ``[m, k]`` solves, pairwise recouple.
    By the backend the profile names, as ec_util.fused_program picks
    the RS kernel (no look at what this host has): on ``pallas`` the
    whole chain is ONE Mosaic kernel with a VMEM-resident working set
    (:func:`build_encode_kernel`); a plain-XLA backend cannot run a
    Mosaic kernel and takes the same three stages as XLA ops around
    the gf_jax matvec (:func:`build_encode_fast`). Built once per
    profile and shared by every codec object of it."""
    key = codec.flush_key()
    with _served_lock:
        fn = _served_encoders.get(key)
        if fn is None:
            if codec.backend == "pallas":
                fn = build_encode_kernel(codec)
            else:
                from ceph_tpu.ops.gf_jax import matvec_device
                fn = build_encode_fast(codec,
                                       matvec_device=matvec_device)
            _served_encoders[key] = fn
    return fn


def flush_decode_table(codec, present: tuple, want: tuple):
    """The operand that makes the one decode program serve erasure
    signature (``present``: the k chunks read, sorted; ``want``: the
    chunks to rebuild): the linearized ``[len(want)*ssc, k*ssc]``
    transform of the layered decode (score-ordered planes, probed once
    from the host oracle, models/clay._decode_matrix) expanded to its
    GF(2) bit matrix, int8, resident on the device. Returns
    ``(table, built)``; cached per (profile, signature) for every
    codec object of the profile, as the reference caches ISA decode
    tables (ErasureCodeIsa.cc:226-303)."""
    import jax.numpy as jnp
    built = []

    def build():
        built.append(1)
        n = codec.get_chunk_count()
        erased = tuple(c for c in range(n) if c not in present)
        mat = codec._decode_matrix(tuple(present), erased)
        ssc = codec.sub_chunk_no
        rows = np.concatenate(
            [mat[erased.index(c) * ssc:(erased.index(c) + 1) * ssc]
             for c in want])
        return jnp.asarray(
            bitmatrix.expand_bitmatrix(rows).astype(np.int8))

    table = _served_tables.get_or_build(
        (codec.flush_key(), tuple(present), tuple(want)), build)
    return table, bool(built)


def flush_decode(table, planes):
    """The served path's decode of one signature over a whole flush:
    ``table [8*e*ssc, 8*k*ssc] int8`` (an OPERAND: one compiled
    program per shape serves every signature), ``planes [k*ssc, L]
    uint8 -> [e*ssc, L]``: one bit-sliced GF(2^8) matmul on the MXU,
    stepped over lane blocks. Safe to call inside an outer jit."""
    import jax
    from ceph_tpu.ops.gf_jax import _bitsliced_matvec_device
    rows, lanes = planes.shape
    block = min(lanes, DECODE_LANE_BLOCK)
    if lanes == block:
        return _bitsliced_matvec_device(table, planes)
    steps = lanes // block
    blocks = planes.reshape(rows, steps, block).transpose(1, 0, 2)
    out = jax.lax.map(
        lambda blk: _bitsliced_matvec_device(table, blk), blocks)
    return out.transpose(1, 0, 2).reshape(table.shape[0] // 8, lanes)


class ClayDeviceCodec:
    """Per-codec cache of compiled layered transforms, keyed by the
    padded erased-node signature (bounded: C(k+m, m) signatures exist
    and each holds a compiled executable)."""

    def __init__(self, codec) -> None:
        from ceph_tpu.utils.lru import BoundedLRU
        self.codec = codec
        self._fns: BoundedLRU = BoundedLRU(64)

    def transform(self, erased: frozenset[int], c_in: np.ndarray):
        """c_in: [q*t, ssc, L] uint8 (numpy or device array); returns
        the completed node array (device)."""
        import time as _time

        import jax.numpy as jnp

        from ceph_tpu.utils.device_telemetry import telemetry

        def build():
            # a signature rebuilt after LRU eviction IS a recompile in
            # the bug-class sense: the cache bound is undersized for
            # the live signature set
            t0 = _time.perf_counter()
            fn = build_transform(self.codec, erased)
            telemetry().note_compile(
                f"clay_transform(k={self.codec.k},m={self.codec.m})"
                f"er={sorted(erased)}", _time.perf_counter() - t0)
            return fn

        fn = self._fns.get_or_build(erased, build)
        return fn(jnp.asarray(c_in))
