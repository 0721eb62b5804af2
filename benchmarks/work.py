"""The least work the chip has to do for what the engine flushed,
counted from shapes (object size, k, m, stripe unit) and never from
what today's implementation happens to move.

The bound of both rooflines is HBM: a GF(2^8) multiply has no published
peak on the chip, and how many MXU operations stand in for one is the
implementation's choice; the bytes are what every implementation has to
move. Each function returns bytes for ``ops`` operations of one cell.
"""

from __future__ import annotations


def padded_object_bytes(object_bytes: int, k: int, stripe_unit: int
                        ) -> int:
    """An object as the pool stores it: whole stripes."""
    width = k * stripe_unit
    return -(-object_bytes // width) * width


def encode_hbm_bytes(ops: int, object_bytes: int, k: int, m: int,
                     stripe_unit: int) -> float:
    """Encode and checksum ``ops`` objects: the k data shards are read
    once, the m parity shards written once (the crcs are 4 bytes a
    shard and are left out)."""
    user = padded_object_bytes(object_bytes, k, stripe_unit)
    return float(ops) * (user + user * m / k)


def decode_hbm_bytes(ops: int, object_bytes: int, k: int, m: int,
                     stripe_unit: int) -> float:
    """Reconstruct for ``ops`` degraded reads: k surviving shards are
    read once. What is written (one or two rebuilt shards an op) is
    left out, so the share reads a little low, never high."""
    del m
    return float(ops) * padded_object_bytes(object_bytes, k,
                                            stripe_unit)


WORK = {"encode_hbm_bytes": encode_hbm_bytes,
        "decode_hbm_bytes": decode_hbm_bytes}
