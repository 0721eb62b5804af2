"""The plain reference of a Clay pool: what its stored bytes have to be.

Independent of the program under test: this file imports numpy and the
GF(2^8) tables and crc of ``reference.py`` (which imports numpy alone),
nothing of ``ceph_tpu``. Written from the published algorithm (Vajha et
al., "Clay Codes: Moulding MDS Codes to Yield an MSR Code", FAST'18;
upstream ``src/erasure-code/clay/ErasureCodeClay.{h,cc}``), the
straightforward way:

- parameters k, m, d: q = d-k+1, nu pads k+m to a multiple of q with
  virtual all-zero chunks, t = (k+m+nu)/q; a chunk is an array of
  q^t sub-chunks. The profile of ``clay_k8m4d11_13osd`` gives q = 4,
  t = 3, nu = 0 and 64 sub-chunks of 64 bytes in a 4096-byte chunk;
- the k+m+nu nodes stand on a q x t grid, node = y*q + x (data chunks
  first, then the virtual ones, then parity); sub-chunk z of a node is
  addressed by the plane vector of z: its t base-q digits, most
  significant first, digit y being the plane's "dot" in column y;
- node (x, y) in plane z is COUPLED with node (z_y, y) in the plane
  that has digit y changed to x. The pair's two coupled values C (what
  is stored) and two uncoupled values U are one codeword of the fixed
  (2, 2) pair code, slots in the order (C of the higher-x member, C of
  the lower, U of the higher, U of the lower); a node on its plane's
  dot (z_y = x) has U = C;
- in every plane, the U values of all nodes are one codeword of the
  scalar MDS code with k+nu data and m parity symbols;
- both codes are ``reed_sol_van`` AS THE PROGRAM SERVES IT, the matrix
  ``reference.coding_matrix`` states: the (k+m) x k Vandermonde matrix
  V[i][j] = i^j over GF(2^8) (0x11d), column-reduced to systematic
  form, NOT rescaled as upstream jerasure does (PERF.md, Open
  questions): ``[4, 8]`` for the planes, ``[[3, 2], [2, 3]]`` for the
  pairs;
- decode, and encode as the decode of the m parity nodes: planes in
  the order of their "intersection score" (how many erased nodes sit
  on the plane's dots); per plane the intact nodes' U from stored C
  (their partner's C, where the partner is erased, was rebuilt in a
  plane of lower score), the erased nodes' U by the MDS code, then the
  erased nodes' C from U;
- layout of an object as ``reference.py`` has it: zero-padded to whole
  stripes of k chunks of ``stripe_unit`` bytes; the code runs per
  stripe (8 chunks in, 12 chunks out); shard i is chunk i of every
  stripe, concatenated. Here every stripe is computed at once: the
  same plane of all stripes side by side.

It does NOT define ``rebuild_read_bytes``: a client's degraded read of
a whole object cannot read less than k chunks; the d/(d-k+1) = 2.75
chunk repair read belongs to the recovery cell and comes with it.
Crcs stay ``reference.crc32c``.
"""

from __future__ import annotations

import numpy as np

import reference
from reference import MUL, gf_inv, gf_mul


# -- small GF(2^8) linear algebra ----------------------------------------

def _gf_inverse(mat: list[list[int]]) -> list[list[int]]:
    """Inverse of a small square matrix, Gauss-Jordan."""
    n = len(mat)
    a = [list(row) + [int(i == j) for j in range(n)]
         for i, row in enumerate(mat)]
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r][col])
        a[col], a[piv] = a[piv], a[col]
        inv = gf_inv(a[col][col])
        a[col] = [gf_mul(v, inv) for v in a[col]]
        for r in range(n):
            f = a[r][col]
            if r != col and f:
                a[r] = [v ^ gf_mul(f, w) for v, w in zip(a[r], a[col])]
    return [row[n:] for row in a]


def _gf_matmul(a: list[list[int]], b: list[list[int]]
               ) -> list[list[int]]:
    out = []
    for row in a:
        acc = [0] * len(b[0])
        for coef, brow in zip(row, b):
            if coef:
                acc = [v ^ gf_mul(coef, w) for v, w in zip(acc, brow)]
        out.append(acc)
    return out


def solve_matrix(coding: list[list[int]], known: list[int],
                 want: list[int]) -> list[list[int]]:
    """For the systematic code with generator [I; coding] (symbols
    0..k-1 data, k.. parity): the matrix that gives the symbols
    ``want`` from the k symbols ``known``."""
    k = len(coding[0])
    gen = [[int(i == j) for j in range(k)] for i in range(k)] + \
        [list(row) for row in coding]
    to_data = _gf_inverse([gen[s] for s in known])
    return _gf_matmul([gen[s] for s in want], to_data)


def _combine(matrix_row: list[int], vectors: list[np.ndarray]
             ) -> np.ndarray:
    acc = np.zeros_like(vectors[0])
    for coef, vec in zip(matrix_row, vectors):
        if coef:
            acc ^= MUL[coef][vec]
    return acc


# -- the code --------------------------------------------------------------

class Clay:
    """The code of one profile; ``decode_layered`` is the whole of it."""

    def __init__(self, k: int, m: int, d: int) -> None:
        if not k <= d <= k + m - 1:
            raise ValueError(f"d={d} outside [{k}, {k + m - 1}]")
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.nodes = self.q * self.t
        self.sub_chunks = self.q ** self.t
        self.mds = reference.coding_matrix(k + self.nu, m)
        self.pair = reference.coding_matrix(2, 2)
        self._solved: dict = {}

    def node_of(self, chunk: int) -> int:
        """Parity chunks stand behind the nu virtual nodes."""
        return chunk if chunk < self.k else chunk + self.nu

    def plane_vector(self, z: int) -> list[int]:
        digits = [0] * self.t
        for i in range(self.t):
            digits[self.t - 1 - i] = z % self.q
            z //= self.q
        return digits

    def _pair(self, known: tuple, want: tuple) -> list[list[int]]:
        key = ("pair", known, want)
        if key not in self._solved:
            self._solved[key] = solve_matrix(self.pair, list(known),
                                             list(want))
        return self._solved[key]

    def _mds(self, intact: tuple, erased: tuple) -> list[list[int]]:
        key = ("mds", intact, erased)
        if key not in self._solved:
            self._solved[key] = solve_matrix(self.mds, list(intact),
                                             list(erased))
        return self._solved[key]

    def decode_layered(self, c: list[list[np.ndarray]],
                       erased: set[int]) -> None:
        """Fill the erased nodes of ``c[node][plane]`` (vectors of
        equal length: that sub-chunk of every stripe) in place."""
        q, t = self.q, self.t
        erased = set(erased)
        for node in range(self.k + self.nu, self.nodes):
            if len(erased) >= self.m:
                break
            erased.add(node)         # pad to exactly m erasures
        if len(erased) > self.m:
            raise ValueError(f"{len(erased)} erasures > m={self.m}")
        intact = tuple(n for n in range(self.nodes) if n not in erased)
        gone = tuple(sorted(erased))
        planes = [self.plane_vector(z) for z in range(self.sub_chunks)]
        score = [sum(1 for n in erased if n % q == zv[n // q])
                 for zv in planes]
        u = [[None] * self.sub_chunks for _ in range(self.nodes)]
        for level in range(max(score) + 1):
            mine = [z for z in range(self.sub_chunks)
                    if score[z] == level]
            for z in mine:
                zv = planes[z]
                for node in intact:
                    x, y = node % q, node // q
                    if zv[y] == x:
                        u[node][z] = c[node][z]
                        continue
                    partner = y * q + zv[y]
                    z_p = z + (x - zv[y]) * q ** (t - 1 - y)
                    # slots: 0, 1 the pair's C (higher x first),
                    # 2, 3 their U
                    mine_c, its_c = (0, 1) if x > zv[y] else (1, 0)
                    pair = self._pair((mine_c, its_c), (2 + mine_c,))
                    u[node][z] = _combine(
                        pair[0], [c[node][z], c[partner][z_p]])
                solve = self._mds(intact, gone)
                known = [u[n][z] for n in intact]
                for row, node in zip(solve, gone):
                    u[node][z] = _combine(row, known)
            for z in mine:
                zv = planes[z]
                for node in gone:
                    x, y = node % q, node // q
                    if zv[y] == x:
                        c[node][z] = u[node][z]
                        continue
                    partner = y * q + zv[y]
                    z_p = z + (x - zv[y]) * q ** (t - 1 - y)
                    mine_c, its_c = (0, 1) if x > zv[y] else (1, 0)
                    if partner not in erased:
                        # own C from the partner's C and own U
                        pair = self._pair((its_c, 2 + mine_c),
                                          (mine_c,))
                        c[node][z] = _combine(
                            pair[0], [c[partner][z_p], u[node][z]])
                    else:
                        # both erased: the pair's C from its two U;
                        # the partner's U belongs to plane z_p, of
                        # this same level
                        pair = self._pair((2 + mine_c, 2 + its_c),
                                          (mine_c,))
                        c[node][z] = _combine(
                            pair[0], [u[node][z], u[partner][z_p]])


_CODES: dict[tuple[int, int, int], Clay] = {}


def code(pool: dict) -> Clay:
    key = (pool["k"], pool["m"], pool.get("d", pool["k"] + pool["m"] - 1))
    if key not in _CODES:
        _CODES[key] = Clay(*key)
    return _CODES[key]


def _grid(clay: Clay, chunks: dict[int, np.ndarray], stripes: int,
          unit: int) -> list[list[np.ndarray]]:
    """``c[node][plane]``: that sub-chunk of every stripe, side by
    side; zeros for the nodes not given (virtual or erased)."""
    sub = unit // clay.sub_chunks
    zero = np.zeros(stripes * sub, dtype=np.uint8)
    grid = [[zero] * clay.sub_chunks for _ in range(clay.nodes)]
    for chunk, stream in chunks.items():
        planes = np.ascontiguousarray(
            np.asarray(stream, dtype=np.uint8)
            .reshape(stripes, clay.sub_chunks, sub).transpose(1, 0, 2))
        grid[clay.node_of(chunk)] = [p.reshape(-1) for p in planes]
    return grid


def _stream(planes: list[np.ndarray], stripes: int) -> np.ndarray:
    """A node's planes back to its shard: chunk after chunk."""
    sub = len(planes[0]) // stripes
    return np.ascontiguousarray(
        np.stack(planes).reshape(len(planes), stripes, sub)
        .transpose(1, 0, 2)).reshape(-1)


def shards(data: bytes, pool: dict) -> list[np.ndarray]:
    """The k+m shards an object's bytes are stored as in ``pool`` (a
    configuration's whole ``pool`` object)."""
    clay = code(pool)
    k, unit = pool["k"], pool["stripe_unit"]
    if unit % clay.sub_chunks:
        raise ValueError(f"stripe_unit {unit} is not a multiple of "
                         f"{clay.sub_chunks} sub-chunks")
    width = k * unit
    buf = np.frombuffer(bytes(data), dtype=np.uint8)
    pad = -len(buf) % width
    if pad or not len(buf):
        buf = np.concatenate(
            [buf, np.zeros(pad or width, dtype=np.uint8)])
    stripes = len(buf) // width
    out = [np.ascontiguousarray(s).reshape(-1) for s in
           buf.reshape(stripes, k, unit).transpose(1, 0, 2)]
    grid = _grid(clay, dict(enumerate(out)), stripes, unit)
    parity = [clay.node_of(c) for c in range(k, k + pool["m"])]
    clay.decode_layered(grid, set(parity))
    return out + [_stream(grid[node], stripes) for node in parity]


def decode(shards_present: dict[int, np.ndarray], wanted: list[int],
           pool: dict) -> dict[int, np.ndarray]:
    """The shards ``wanted`` rebuilt from the shards given (at least k
    of the k+m; every shard not given counts as erased)."""
    clay = code(pool)
    unit = pool["stripe_unit"]
    length = len(next(iter(shards_present.values())))
    stripes = length // unit
    lost = [c for c in range(pool["k"] + pool["m"])
            if c not in shards_present]
    grid = _grid(clay, shards_present, stripes, unit)
    clay.decode_layered(grid, {clay.node_of(c) for c in lost})
    return {c: np.asarray(shards_present[c], dtype=np.uint8)
            if c in shards_present
            else _stream(grid[clay.node_of(c)], stripes)
            for c in wanted}
