"""The least work the chip has to do for what the engine flushed,
counted from shapes (object size, k, m, stripe unit) and never from
what today's implementation happens to move.

The bound of both rooflines is HBM: a GF(2^8) multiply has no published
peak on the chip, and how many MXU operations stand in for one is the
implementation's choice; the bytes are what every implementation has to
move. Each function returns bytes for ``ops`` operations of one cell,
and takes the configuration's whole ``pool`` object and its reference
module: a codec that can rebuild from less than k whole shards states
so there (``rebuild_read_bytes(pool, object_bytes)``), and the roofline
reads the same least work whatever implements it.
"""

from __future__ import annotations


def padded_object_bytes(object_bytes: int, k: int, stripe_unit: int
                        ) -> int:
    """An object as the pool stores it: whole stripes."""
    width = k * stripe_unit
    return -(-object_bytes // width) * width


def encode_hbm_bytes(ops: int, object_bytes: int, pool: dict,
                     ref=None) -> float:
    """Encode and checksum ``ops`` objects: the k data shards are read
    once, the m parity shards written once (the crcs are 4 bytes a
    shard and are left out)."""
    del ref
    k, m = pool["k"], pool["m"]
    user = padded_object_bytes(object_bytes, k, pool["stripe_unit"])
    return float(ops) * (user + user * m / k)


def decode_hbm_bytes(ops: int, object_bytes: int, pool: dict,
                     ref=None) -> float:
    """Reconstruct for ``ops`` degraded reads or recovery rebuilds:
    the least a rebuild has to read, once: what the configuration's
    reference module states, else RS's k surviving shards (an object's
    worth). What is written (one or two rebuilt shards an op) is left
    out, so the share reads a little low, never high."""
    stated = getattr(ref, "rebuild_read_bytes", None)
    if stated is not None:
        return float(ops) * stated(pool, object_bytes)
    return float(ops) * padded_object_bytes(
        object_bytes, pool["k"], pool["stripe_unit"])


WORK = {"encode_hbm_bytes": encode_hbm_bytes,
        "decode_hbm_bytes": decode_hbm_bytes}
