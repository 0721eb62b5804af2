"""Layout/compile seam for the pod-scale sharded EC pipeline (ISSUE 12).

Two pieces every mesh step is built from:

- :class:`SpecLayout` — the per-stage ``PartitionSpec`` table, declared
  ONCE: stage batch, coding matrix, parity/chunks out, crc/csum out,
  gathered (read-reply) out. A step never spells a spec inline; a
  layout change (say a 3D pod mesh) edits one table, not five call
  sites.
- :func:`compile_step` — the compile seam. Every step body exists in
  two semantically identical spellings: a GLOBAL-view ``global_fn``
  (whole-array math; XLA's SPMD partitioner inserts the collectives)
  and a per-shard ``shard_fn`` (explicit ``ppermute``/``psum``/
  ``all_gather``). The seam prefers ``jax.jit`` with ``in_shardings``/
  ``out_shardings`` over the raw ``jax.shard_map`` wrap — the pjit
  route gives the compiler the whole dataflow (it can fuse the
  placement shift into the parity store, overlap the csum
  all-reduce, and skip the per-shard reshape choreography) — and
  takes the shard_map spelling for a step that has no global one, or
  when ``mesh_compile_mode`` forces it.

Both spellings take the coding matrix as an ARGUMENT (spec'd in the
layout table) rather than a closure capture, so a fresh matrix
identity never bakes into a compiled program (the closure-device-array
recompile class the jit-hygiene lint flags — which, since ISSUE 12,
walks shard_map/in_shardings-wrapped callees exactly like plain jit).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class SpecLayout:
    """Canonical PartitionSpecs for the EC pipeline stages, keyed to
    the ('stripe', 'shard') mesh axes (parallel/mesh.py)."""

    stripe_axis: str = "stripe"
    shard_axis: str = "shard"

    def stage_batch(self) -> P:
        """[S, k, C] stripe batches: stripes data-parallel, chunk
        bytes over the shard axis (zero-communication encode)."""
        return P(self.stripe_axis, None, self.shard_axis)

    def coding_matrix(self) -> P:
        """[8m, 8k] expanded bit-matrix: replicated (every chip
        encodes its local bytes against the whole matrix)."""
        return P()

    def chunks_out(self) -> P:
        """[S, n, C] encoded chunks / reconstructed rows: same
        placement as the stage batch (shards stay home)."""
        return P(self.stripe_axis, None, self.shard_axis)

    def csum_out(self) -> P:
        """[n] integrity stat (the hinfo crc role): psum'd over the
        whole mesh, replicated out."""
        return P()

    def gathered_out(self) -> P:
        """[S, w, C] read-reply gather: full chunk bytes at every
        shard position (the ECBackend.cc:1123 reassembly)."""
        return P(self.stripe_axis, None, None)

    def object_batch(self) -> P:
        """[N, n, L] per-object shard batches (deep-scrub verify):
        objects spread over EVERY chip — both mesh axes flattened —
        each chip verifying its objects entirely locally."""
        return P((self.stripe_axis, self.shard_axis), None, None)

    def verdict_out(self) -> P:
        """[N, ...] per-object verdicts (mismatch bitmap / crc
        vector): partitioned like the object batch."""
        return P((self.stripe_axis, self.shard_axis), None)


#: the one process-wide layout table (a pod profile could swap it)
LAYOUT = SpecLayout()


def compile_mode() -> str:
    """auto | pjit | shard_map — env override beats the declared
    Option (the registry-covered knob, ISSUE 12 satellite)."""
    mode = os.environ.get("CEPH_TPU_MESH_COMPILE_MODE")
    if mode:
        return mode
    try:
        from ceph_tpu.utils.config import g_conf
        return g_conf()["mesh_compile_mode"]
    except Exception:
        return "auto"


def _named(mesh: Mesh, specs):
    # PartitionSpec subclasses tuple: test it FIRST or a single spec
    # would be iterated as a tuple of axis names
    if isinstance(specs, P):
        return NamedSharding(mesh, specs)
    if isinstance(specs, tuple):
        return tuple(NamedSharding(mesh, s) for s in specs)
    return NamedSharding(mesh, specs)


def compile_step(mesh: Mesh, *, global_fn=None, shard_fn=None,
                 in_specs, out_specs):
    """Compile one mesh step. Returns ``(compiled, path)`` where
    ``path`` is ``"pjit"`` or ``"shard_map"``.

    ``global_fn`` is the whole-array spelling (compiled with
    ``jax.jit`` + in/out shardings); ``shard_fn`` is the per-shard
    spelling with explicit collectives (wrapped in
    ``jax.shard_map``). Both receive the same
    argument list; out_specs is a spec (or tuple of specs) matching
    the output pytree. ``mesh_compile_mode`` / the
    ``CEPH_TPU_MESH_COMPILE_MODE`` env pin one route for A/B runs."""
    mode = compile_mode()
    want_pjit = mode in ("auto", "pjit") and global_fn is not None
    if mode == "pjit" and not want_pjit:
        raise RuntimeError(
            "mesh_compile_mode=pjit but the step has no global "
            "spelling")
    if want_pjit:
        compiled = jax.jit(global_fn,
                           in_shardings=_named(mesh, in_specs),
                           out_shardings=_named(mesh, out_specs))
        path = "pjit"
    else:
        if shard_fn is None:
            raise RuntimeError("step has no shard_map spelling and "
                               f"mode={mode} rules out pjit")
        compiled = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))
        path = "shard_map"
    try:
        from ceph_tpu.utils.device_telemetry import telemetry
        telemetry().note_mesh_compile(path)
    except Exception:
        pass                      # accounting never costs the build
    return compiled, path
