"""Versioned binary wire encoding — the src/include/encoding.h role.

The reference serializes every map/message/txn with ENCODE_START /
ENCODE_FINISH versioned sections and little-endian primitive encoders.
Same contract here: explicit little-endian primitives, length-prefixed
bytes/str, and versioned sections that let a decoder skip trailing
fields added by newer encoders (forward/backward compatibility —
encoding.h's compat_version semantics).

No pickle anywhere: wire bytes are data, never code.
"""

from __future__ import annotations

import struct

_U8 = struct.Struct("<B")
_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")
_I32 = struct.Struct("<i")
_I64 = struct.Struct("<q")
_F64 = struct.Struct("<d")

#: a part at least this long stays a buffer of its own in the scatter
#: list; shorter ones are joined into the run around them. Under it a
#: copy costs less than what every further part costs the sender (a
#: list entry, a crc call, a socket write); above it the copy is what
#: a bulk message pays for.
SCATTER_MIN = 16 * 1024


class Encoder:
    """Builds an encoding as a scatter list: runs of small pieces,
    joined when the run closes, and every piece of ``SCATTER_MIN``
    bytes or more by reference. ``getvalue()`` is the one join."""

    def __init__(self) -> None:
        self._parts: list[bytes] = []   # closed: joined runs, large pieces
        self._run: list[bytes] = []     # the open run of small pieces

    # primitives (little-endian, like encoding.h)
    def u8(self, v: int) -> "Encoder":
        self._run.append(_U8.pack(v)); return self

    def u16(self, v: int) -> "Encoder":
        self._run.append(_U16.pack(v)); return self

    def u32(self, v: int) -> "Encoder":
        self._run.append(_U32.pack(v)); return self

    def u64(self, v: int) -> "Encoder":
        self._run.append(_U64.pack(v)); return self

    def i32(self, v: int) -> "Encoder":
        self._run.append(_I32.pack(v)); return self

    def i64(self, v: int) -> "Encoder":
        self._run.append(_I64.pack(v)); return self

    def f64(self, v: float) -> "Encoder":
        self._run.append(_F64.pack(v)); return self

    def bool(self, v: bool) -> "Encoder":
        return self.u8(1 if v else 0)

    def _put(self, part: bytes) -> None:
        if len(part) < SCATTER_MIN:
            self._run.append(part)
        else:
            self._close_run()
            self._parts.append(part)

    def _close_run(self) -> None:
        if self._run:
            self._parts.append(b"".join(self._run))
            self._run = []

    def bytes(self, v) -> "Encoder":
        """A length-prefixed value: ``bytes`` (or anything ``bytes()``
        takes), or a :class:`Parts`, whose buffers go in as they are."""
        parts = v.parts if isinstance(v, Parts) else (bytes(v),)
        self._run.append(_U32.pack(sum(map(len, parts))))
        for p in parts:
            self._put(p)
        return self

    def str(self, v: str) -> "Encoder":
        return self.bytes(v.encode())

    def list(self, vals, item_fn) -> "Encoder":
        self.u32(len(vals))
        for v in vals:
            item_fn(self, v)
        return self

    def map(self, d: dict, key_fn, val_fn) -> "Encoder":
        self.u32(len(d))
        for k in sorted(d):
            key_fn(self, k)
            val_fn(self, d[k])
        return self

    def str_map(self, d: dict) -> "Encoder":
        return self.map(d, Encoder.str, Encoder.str)

    def section(self, version: int, body: "Encoder",
                compat: int = 1) -> "Encoder":
        """ENCODE_START(version, compat, ...) ... ENCODE_FINISH:
        version + compat bytes + length-prefixed body. ``compat`` is the
        oldest decoder version able to read this encoding; decoders skip
        trailing bytes they don't parse. The body's parts are taken
        over as they are: nesting a section copies no large piece."""
        self.u8(version)
        self.u8(compat)
        return self.bytes(Parts(body.getparts()))

    def getparts(self) -> list[bytes]:
        """The encoded buffers WITHOUT the final join — the sendmsg-
        style scatter list whose concatenation == ``getvalue()``."""
        self._close_run()
        return list(self._parts)

    def getvalue(self) -> bytes:
        return b"".join(self.getparts())


class Parts:
    """An encoded value kept as its scatter list, for a sender that
    nests it in another encoding (``Encoder.bytes`` takes it) and so
    leaves the one join to whoever needs contiguous bytes."""

    __slots__ = ("parts",)

    def __init__(self, parts: list[bytes]) -> None:
        self.parts = parts

    def __len__(self) -> int:
        return sum(map(len, self.parts))

    def __bytes__(self) -> bytes:
        return b"".join(self.parts)


class DecodeError(Exception):
    pass


class Decoder:
    """Reads ``buf[off:end]`` in place: a section's sub-decoder is a
    window of the same buffer, and only a decoded ``bytes`` / ``str``
    value is copied out of it."""

    def __init__(self, buf: bytes, off: int = 0,
                 end: int | None = None) -> None:
        self._buf = buf
        self._off = off
        self._end = len(buf) if end is None else end

    def _advance(self, n: int) -> int:
        """Move past ``n`` bytes; returns where they start."""
        off = self._off
        if off + n > self._end:
            raise DecodeError(
                f"short buffer: need {n} at {off}, have {self._end}")
        self._off = off + n
        return off

    def _num(self, st: struct.Struct):
        return st.unpack_from(self._buf, self._advance(st.size))[0]

    def u8(self) -> int: return self._num(_U8)
    def u16(self) -> int: return self._num(_U16)
    def u32(self) -> int: return self._num(_U32)
    def u64(self) -> int: return self._num(_U64)
    def i32(self) -> int: return self._num(_I32)
    def i64(self) -> int: return self._num(_I64)
    def f64(self) -> float: return self._num(_F64)
    def bool(self) -> bool: return self.u8() != 0

    def bytes(self) -> bytes:
        n = self.u32()
        off = self._advance(n)
        return self._buf[off:off + n]

    def str(self) -> str:
        return self.bytes().decode()

    def list(self, item_fn) -> list:
        return [item_fn(self) for _ in range(self.u32())]

    def map(self, key_fn, val_fn) -> dict:
        n = self.u32()
        return {key_fn(self): val_fn(self) for _ in range(n)}

    def str_map(self) -> dict:
        return self.map(Decoder.str, Decoder.str)

    def section(self, max_supported: int) -> tuple[int, "Decoder"]:
        """DECODE_START: returns (version, sub-decoder over the section
        body). A newer encoding is readable as long as its ``compat``
        floor is within what this reader supports (the known field
        prefix decodes; unknown trailing bytes are skipped). Raises
        DecodeError when the encoder declared itself incompatible, or
        when the body is shorter than its length says."""
        version = self.u8()
        compat = self.u8()
        n = self.u32()
        off = self._advance(n)
        if compat > max_supported:
            raise DecodeError(
                f"encoding v{version} requires decoder >= v{compat}, "
                f"this reader supports <= v{max_supported}")
        return version, Decoder(self._buf, off, off + n)

    def remaining(self) -> int:
        return self._end - self._off

    def eof(self) -> bool:
        return self._off >= self._end
