"""Erasure-code benchmark — CLI-compatible with ``ceph_erasure_code_benchmark``.

Reference: src/test/erasure-code/ceph_erasure_code_benchmark.cc. Same
surface: ``--plugin/-p``, repeated ``--parameter/-P k=v``, ``--size/-S``
(total bytes per op), ``--iterations/-i``, ``--workload/-w encode|decode``,
``--erasures/-e`` (random erasure count) or ``--erased`` (fixed chunk), and
``--erasures-generation exhaustive``. Same output contract (reference
:188,326): one line ``elapsed_seconds <TAB> total_KiB`` — throughput =
KiB/elapsed.

Extra, TPU-first: ``--batch`` objects are encoded per kernel launch
(device-side stripe batching — the per-object loop of the reference becomes
one big lane dimension), and ``--device-resident`` keeps buffers in HBM
between iterations the way the OSD stripe accumulator does, so the number
measures the kernel, not the host link.
"""

from __future__ import annotations

import argparse
import itertools
import random
import sys
import time

import numpy as np

from ceph_tpu.models import instance


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="ec_bench")
    ap.add_argument("--plugin", "-p", default="jerasure")
    ap.add_argument("--parameter", "-P", action="append", default=[],
                    help="profile k=v pairs")
    ap.add_argument("--size", "-S", type=int, default=1 << 20,
                    help="bytes per object per iteration")
    ap.add_argument("--iterations", "-i", type=int, default=10)
    ap.add_argument("--workload", "-w", default="encode",
                    choices=("encode", "decode"))
    ap.add_argument("--erasures", "-e", type=int, default=1)
    ap.add_argument("--erased", type=int, action="append", default=None,
                    help="fixed erased chunk ids")
    ap.add_argument("--erasures-generation", default="random",
                    choices=("random", "exhaustive"))
    ap.add_argument("--batch", type=int, default=1,
                    help="objects per kernel launch (device batching)")
    ap.add_argument("--device-resident", action="store_true",
                    help="keep buffers in HBM between iterations and "
                         "measure by chained slope (TPU only)")
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--seed", type=int, default=42)
    return ap.parse_args(argv)


class ErasureCodeBench:
    """Mirrors ErasureCodeBench::{setup,run,encode,decode} (reference :40-328)."""

    def __init__(self, args) -> None:
        self.args = args
        profile = {}
        for kv in args.parameter:
            key, _, val = kv.partition("=")
            profile[key] = val
        profile.setdefault("backend", args.backend)
        self.profile = profile
        self.codec = instance().factory(args.plugin, profile)
        self.k = self.codec.get_data_chunk_count()
        self.n = self.codec.get_chunk_count()

    def run(self) -> tuple[float, int]:
        if self.args.device_resident:
            if self.args.workload != "encode":
                raise SystemExit(
                    "--device-resident supports encode only")
            return self.encode_device_resident()
        if self.args.workload == "encode":
            return self.encode()
        return self.decode()

    def _make_objects(self):
        rng = np.random.default_rng(self.args.seed)
        return [
            rng.integers(0, 256, size=self.args.size, dtype=np.uint8).tobytes()
            for _ in range(self.args.batch)
        ]

    def encode(self) -> tuple[float, int]:
        objs = self._make_objects()
        want = list(range(self.n))
        # warmup (jit compile) outside the timed region
        self.codec.encode(want, objs[0])
        begin = time.perf_counter()
        total = 0
        for _ in range(self.args.iterations):
            for data in objs:
                self.codec.encode(want, data)
                total += len(data)
        elapsed = time.perf_counter() - begin
        return elapsed, total // 1024

    def encode_device_resident(self) -> tuple[float, int]:
        """Device-resident chained-slope encode (shared machinery in
        bench/measure.py): the stripe batch stays in HBM between
        iterations the way the OSD stripe accumulator feeds the chip.
        Matrix codecs on a TPU backend only."""
        import jax
        import jax.numpy as jnp

        if jax.default_backend() == "cpu":
            raise SystemExit("--device-resident needs a TPU backend")
        mat = getattr(self.codec, "coding_matrix", None)
        if mat is None:
            raise SystemExit(
                "--device-resident needs a matrix codec "
                "(jerasure/isa/shec)")
        from ceph_tpu.bench.measure import chained_slope
        from ceph_tpu.ops import gf_pallas
        mat = np.asarray(mat, dtype=np.uint8)
        total_bytes = self.args.size * self.args.batch
        n_lanes = max(total_bytes // self.k, 1)
        rng = np.random.default_rng(self.args.seed)
        data = jnp.asarray(rng.integers(
            0, 256, size=(self.k, n_lanes), dtype=np.uint8))
        m_out = mat.shape[0]

        def step(dd):
            # matvec_device pads/tiles arbitrary lane counts — a raw
            # _matvec_padded call silently skips tail lanes
            p = gf_pallas.matvec_device(mat, dd)
            return dd.at[0:1].set(p[0:1])

        slope = chained_slope(
            step, data,
            min_traffic_bytes=n_lanes * (self.k + m_out))
        elapsed = slope * self.args.iterations
        total = n_lanes * self.k * self.args.iterations
        return elapsed, total // 1024

    def _erasure_patterns(self):
        if self.args.erased:
            return itertools.repeat(tuple(self.args.erased))
        if self.args.erasures_generation == "exhaustive":
            combos = list(itertools.combinations(range(self.n),
                                                 self.args.erasures))
            return itertools.cycle(combos)
        rnd = random.Random(self.args.seed)

        def gen():
            while True:
                yield tuple(rnd.sample(range(self.n), self.args.erasures))
        return gen()

    def decode(self) -> tuple[float, int]:
        data = self._make_objects()[0]
        encoded = self.codec.encode(list(range(self.n)), data)
        chunk_size = len(encoded[0])
        patterns = self._erasure_patterns()
        # warmup
        first = next(patterns)
        avail = {i: encoded[i] for i in range(self.n) if i not in first}
        self.codec.decode(list(first), avail, chunk_size)
        begin = time.perf_counter()
        total = 0
        for _, lost in zip(range(self.args.iterations), patterns):
            avail = {i: encoded[i] for i in range(self.n) if i not in lost}
            out = self.codec.decode(list(lost), avail, chunk_size)
            assert all(len(v) == chunk_size for v in out.values())
            total += len(data)
        elapsed = time.perf_counter() - begin
        return elapsed, total // 1024


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])
    bench = ErasureCodeBench(args)
    elapsed, kib = bench.run()
    # output contract of the reference benchmark (:188)
    print(f"{elapsed:f}\t{kib}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
