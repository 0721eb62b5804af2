"""Shared codec logic — semantic equivalent of ``ceph::ErasureCode``.

Reference: src/erasure-code/ErasureCode.{h,cc}. Reproduces the base-class
behaviors the plugins rely on:

- chunk padding/alignment: ``SIMD_ALIGN = 32`` (ErasureCode.cc:31); here the
  alignment doubles as the TPU lane-friendly unit and chunk sizes are also
  rounded so the bit-plane width stays a multiple of 8;
- ``encode_prepare`` splits + zero-pads input into k aligned chunks
  (ErasureCode.cc:137-172);
- generic ``encode`` = prepare -> ``encode_chunks`` (ErasureCode.cc:174-190);
- ``_decode`` copies trivially when all wanted chunks are present, else
  calls ``decode_chunks`` (ErasureCode.cc:198-234);
- default ``minimum_to_decode`` = any k available chunks, preferring the
  wanted ones themselves (ErasureCode.cc:89-123);
- ``chunk_mapping`` remap support (ErasureCode.cc:260-279);
- profile parsing helpers to_int/to_bool (ErasureCode.cc:281-329).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from ceph_tpu.models.interface import (
    ErasureCodeError,
    ErasureCodeInterface,
    ErasureCodeProfile,
)

#: Reference SIMD_ALIGN (ErasureCode.cc:31). Chunks are padded so
#: chunk_size % SIMD_ALIGN == 0 — which also keeps device tiles happy.
SIMD_ALIGN = 32


class ErasureCode(ErasureCodeInterface):
    """Base class implementing the generic split/pad/assemble machinery."""

    #: what the codec states to the device engine's seams
    #: (osd/ec_util.flush_kind): ``"matrix"``: a flush is one
    #: ``[m, k]`` GF matrix over the byte stream (MatrixErasureCode
    #: without a chunk mapping); ``"layered"``: a flush is one layered
    #: program over plane-major lanes (clay); None: the host codec
    #: serves, stripe by stripe
    device_flush: str | None = None

    def __init__(self) -> None:
        self._profile: ErasureCodeProfile = {}
        self.chunk_mapping: list[int] = []

    # -- profile helpers (reference: ErasureCode.cc:281-329) ---------------

    @staticmethod
    def to_int(name: str, profile: Mapping[str, str], default: int) -> int:
        val = profile.get(name, None)
        if val in (None, ""):
            return default
        try:
            return int(val)
        except (TypeError, ValueError):
            raise ErasureCodeError(f"{name}={val!r} is not a valid integer")

    @staticmethod
    def to_bool(name: str, profile: Mapping[str, str], default: bool) -> bool:
        val = profile.get(name, None)
        if val in (None, ""):
            return default
        if isinstance(val, bool):
            return val
        return str(val).lower() in ("yes", "true", "1")

    # -- geometry ----------------------------------------------------------

    @property
    def k(self) -> int:
        return self.get_data_chunk_count()

    @property
    def m(self) -> int:
        return self.get_coding_chunk_count()

    def get_profile(self) -> ErasureCodeProfile:
        return self._profile

    def get_chunk_size(self, stripe_width: int) -> int:
        """Pad so every chunk is SIMD_ALIGN-aligned (ErasureCode base
        behavior; plugins with stricter needs override)."""
        k = self.get_data_chunk_count()
        alignment = k * SIMD_ALIGN
        padded = -(-stripe_width // alignment) * alignment
        return padded // k

    # -- chunk index remap (reference: ErasureCode.cc:260-279) -------------

    def _chunk_index(self, i: int) -> int:
        return self.chunk_mapping[i] if self.chunk_mapping else i

    def get_chunk_mapping(self) -> list[int]:
        return list(self.chunk_mapping)

    # -- minimum_to_decode (reference: ErasureCode.cc:89-123) --------------

    def _minimum_to_decode_chunks(
        self, want_to_read: Sequence[int], available: Sequence[int]
    ) -> list[int]:
        want = set(want_to_read)
        avail = set(available)
        if want <= avail:
            return sorted(want)
        k = self.get_data_chunk_count()
        if len(avail) < k:
            raise ErasureCodeError(
                f"cannot decode: want {sorted(want)}, only "
                f"{sorted(avail)} available, need {k}", errno_=5)
        # prefer wanted chunks that are available, fill with others
        chosen = sorted(want & avail)
        for c in sorted(avail - want):
            if len(chosen) >= k:
                break
            chosen.append(c)
        return sorted(chosen[:k])

    def minimum_to_decode(
        self, want_to_read: Sequence[int], available: Sequence[int]
    ):
        chunks = self._minimum_to_decode_chunks(want_to_read, available)
        # scalar codes: whole chunk = sub-chunk range (0, 1)
        return {c: [(0, self.get_sub_chunk_count())] for c in chunks}

    # -- encode (reference: ErasureCode.cc:137-190) ------------------------

    def encode_prepare(self, data: bytes | np.ndarray) -> np.ndarray:
        """Split + zero-pad input into a [k, chunk_size] array
        (reference: encode_prepare, ErasureCode.cc:137-172)."""
        buf = np.frombuffer(bytes(data), dtype=np.uint8) \
            if not isinstance(data, np.ndarray) else data.astype(np.uint8, copy=False).ravel()
        k = self.get_data_chunk_count()
        chunk_size = self.get_chunk_size(len(buf))
        padded = np.zeros(k * chunk_size, dtype=np.uint8)
        padded[: len(buf)] = buf
        return padded.reshape(k, chunk_size)

    def encode(self, want_to_encode, data):
        chunks = self.encode_prepare(data)
        k = self.get_data_chunk_count()
        n = self.get_chunk_count()
        chunk_map = {self._chunk_index(i): chunks[i] for i in range(k)}
        coded = self.encode_chunks(list(range(n)), chunk_map)
        chunk_map.update(coded)
        return {i: chunk_map[i] for i in want_to_encode if i in chunk_map}

    # -- decode (reference: ErasureCode.cc:198-234) ------------------------

    def decode(self, want_to_read, chunks, chunk_size):
        have = set(chunks)
        want = list(want_to_read)
        if set(want) <= have:
            return {i: np.asarray(chunks[i], dtype=np.uint8) for i in want}
        return self.decode_chunks(want, chunks)

    def _decode_via_matrix(self, want_to_read, chunks):
        raise NotImplementedError
