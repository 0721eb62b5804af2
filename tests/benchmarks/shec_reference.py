"""A configuration's own plain reference, as a later PR would bring
it: the stored shards of a ``plugin=shec technique=single`` pool.

Imports numpy and the benchmark's ``reference`` (GF(2^8), the
Vandermonde matrix, the layout) and nothing of ``ceph_tpu``. SHEC
(upstream ``src/erasure-code/shec/ErasureCodeShec.cc``) starts from
the systematic Vandermonde coding matrix and keeps, in parity row
``r`` of ``m``, only the circular window of data columns
``[r*k/m, (r+c)*k/m) mod k``; every other coefficient is zero. ``c``
is the profile key the RS reference knows nothing of.
"""

from __future__ import annotations

import reference


def coding_matrix(k: int, m: int, c: int) -> list[list[int]]:
    rows = []
    for r, row in enumerate(reference.coding_matrix(k, m)):
        keep = {col % k for col in range(r * k // m, (r + c) * k // m)}
        rows.append([coef if col in keep else 0
                     for col, coef in enumerate(row)])
    return rows


def shards(data: bytes, pool: dict):
    if pool.get("technique") != "single":
        raise ValueError("this reference states technique=single only")
    k, m = pool["k"], pool["m"]
    return reference.encode(data, k, m, pool["stripe_unit"],
                            matrix=coding_matrix(k, m, pool["c"]))


def rebuild_read_bytes(pool: dict, object_bytes: int) -> float:
    """The least a single-shard rebuild reads: the narrowest window
    that covers the shard and its parity, not k whole shards."""
    k, m, c = pool["k"], pool["m"], pool["c"]
    widths = [(r + c) * k // m - r * k // m for r in range(m)]
    width = k * pool["stripe_unit"]
    shard = -(-object_bytes // width) * pool["stripe_unit"]
    return float(min(widths) * shard)
