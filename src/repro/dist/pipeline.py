"""Pipeline parallelism: GPipe-style microbatched stage execution.

``pipeline_apply`` runs ``x``'s microbatches through ``nstages`` identical
stages whose parameters are sharded over a mesh axis (one stage per mesh
slice).  Schedule: the classic M + P - 1 tick wavefront — at tick t, stage p
processes microbatch t - p; activations advance one stage per tick via
``lax.ppermute`` (the only wire traffic: one microbatch of activations per
tick per stage boundary).  With the default f32 hops, numerics are exactly
the sequential composition (same ops, same order), which is what the dist
test asserts.

``wire_fmt`` compresses the inter-stage hops through the wire codec (the
``QuantPolicy.pipe_act`` surface): the sending stage encodes its output
activations to the format's packed bits, ``ppermute`` moves the narrow
payload, and the receiving stage decodes back to f32 — exactly the
transport-narrow / compute-wide split ``compressed_psum`` makes for
gradients, cutting the per-hop wire bytes 2-4x (t16/bf16 vs t8/e4m3).
Unlike gradient sums, stage activations feed *directly* into the next
matmul, so each hop injects one quantisation error per element per stage
boundary; the quality/wire-bytes trade is measured in
``benchmarks/collectives_bench`` and the default stays f32 (exact).

Bubble fraction is (P-1)/(M+P-1); callers pick M >> P to amortise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.core.formats import special_fraction, wire_format

from . import faults

IS_STUB = False


def _hop_codec(name, last_n):
    """(encode, decode) for one stage-hop rung, block padding folded in;
    ``(None, None)`` for the exact f32 rung."""
    if name == "f32":
        return None, None
    from repro.core.tables import decode_table_f32
    from repro.quant import blockscale
    from .collectives import wire_codec

    wf = wire_format(name)
    if wf.supports_lut_decode and wf.name != "bf16":
        # build the decode LUT *here*, outside the shard_map body: an
        # eager shard_map trace cannot host the table construction
        # (ensure_compile_time_eval only escapes jit traces).  The
        # encode side needs no such care: wire_codec's fast encode
        # tables are numpy-built (repro.core.tables), trace-safe.
        # (Block-scaled formats tabulate their element format.)
        decode_table_f32(wf.elem_name if wf.is_block_scaled else wf.name)
    encode, decode = wire_codec(wf.name)
    if wf.is_block_scaled:
        # block codec: zero-pad the hop's last axis to a 32-multiple on
        # send, slice back on arrival (stages preserve shapes, so the
        # logical hop width is x's trailing dim)
        enc0, dec0 = encode, decode
        encode = lambda v: enc0(blockscale.pad_block(v))
        decode = lambda m, _n=last_n: dec0(m)[..., :_n]
    return encode, decode


def pipeline_apply(stage_fn, stage_params, x, *, mesh, axis: str = "pipe",
                   wire_fmt=None, guard=None):
    """Run microbatches through parameter-sharded pipeline stages.

    Args:
      stage_fn: ``(stage_weights, h) -> h`` for one stage (shapes preserved).
      stage_params: pytree whose leaves have a leading ``nstages`` dim.
      x: ``[M, microbatch, ...]`` input microbatches.
      mesh: mesh containing ``axis``; its other axes are untouched.
      axis: mesh axis name the stages are laid out over.
      wire_fmt: None/"f32" for exact f32 stage hops, or any registered
        <=16-bit wire format ('t8', 't16', 'e4m3', 'e5m2', 'bf16', or a
        block-scaled 'mxe4m3'/'mxe5m2'/'mxt8' container) to compress the
        inter-stage activation traffic (QuantPolicy.pipe_act).
      guard: optional :class:`~repro.quant.policy.GuardPolicy`.  Arms the
        per-tick fault guards (DESIGN.md §8): the sender health-checks its
        encoded hop payload (special fraction + relative rms error), the
        trip flag is psum'd over ``axis`` so every stage escalates the same
        tick, and a tripped hop re-sends at the ladder's next rung (one
        step wider; f32 = exact).  Arriving activations pass the
        containment rail: non-finite / over-``contain_abs`` elements are
        zeroed and counted (``pipe.contained``) instead of flowing into the
        next stage's matmul.

    Returns the output of the final stage for every microbatch, replicated
    over ``axis`` — shape ``[M, microbatch, ...]``.
    """
    from jax.sharding import PartitionSpec as P

    if wire_fmt is not None and wire_format(wire_fmt).name != "f32":
        name = wire_format(wire_fmt).name
        hop_encode, hop_decode = _hop_codec(name, x.shape[-1])
    else:
        name = "f32"
        hop_encode = hop_decode = None

    esc_name = None
    esc_encode = esc_decode = None
    if guard is not None and hop_encode is not None:
        rungs = guard.ladder_from(name)
        if len(rungs) > 1:
            esc_name = rungs[1]  # one step wider per tick keeps the trace small
            esc_encode, esc_decode = _hop_codec(esc_name, x.shape[-1])

    nstages = mesh.shape[axis]
    M = x.shape[0]
    lead = jax.tree.leaves(stage_params)[0].shape[0]
    assert lead == nstages, f"stage_params lead dim {lead} != mesh axis {nstages}"

    def contain(recv):
        if guard is None or not guard.contain_hops:
            return recv
        bad = ~jnp.isfinite(recv) | (jnp.abs(recv) > guard.contain_abs)
        telemetry.emit("pipe.contained", jnp.sum(bad, dtype=jnp.float32))
        return jnp.where(bad, jnp.zeros((), recv.dtype), recv)

    def plain_hop(out, perm):
        # exact f32 hop (still subject to injected hop faults + containment)
        if telemetry.enabled():
            telemetry.emit(
                "pipe.hop_bytes", float(out.size * out.dtype.itemsize))
        return contain(faults.corrupt_hop(jax.lax.ppermute(out, axis, perm), axis))

    def coded_hop(out, perm, dtype):
        # narrow wire: encode once, move packed bits, decode on
        # arrival (the pipe_act compressed-hop surface)
        wire = hop_encode(out)
        if telemetry.enabled():
            telemetry.emit(
                "pipe.hop_bytes", float(wire.size * wire.dtype.itemsize))
        wire = faults.corrupt_hop(jax.lax.ppermute(wire, axis, perm), axis)
        return contain(hop_decode(wire).astype(dtype))

    def guarded_hop(out, perm, dtype):
        # sender-side health check -> ring-uniform trip -> one-rung-wider
        # resend (the psum must precede the cond; a collective inside a
        # divergent branch deadlocks the stage ring)
        outf = out.astype(jnp.float32)
        wire = hop_encode(outf)
        q = hop_decode(wire)
        spec = special_fraction(wire, name)
        fin = jnp.isfinite(q)
        errq = jnp.where(fin, q - outf, jnp.float32(0))
        rel = jnp.sqrt(jnp.mean(jnp.square(errq))) / (
            jnp.sqrt(jnp.mean(jnp.square(outf))) + jnp.float32(1e-12))
        trip_local = (spec > guard.max_special_frac) | (rel > guard.max_rel_err)
        trip = jax.lax.psum(trip_local.astype(jnp.float32), axis) > 0

        def base():
            w = faults.corrupt_hop(jax.lax.ppermute(wire, axis, perm), axis)
            return hop_decode(w)

        def widened():
            if esc_encode is None:  # escalation rung is f32: exact hop
                return faults.corrupt_hop(jax.lax.ppermute(outf, axis, perm), axis)
            w = faults.corrupt_hop(
                jax.lax.ppermute(esc_encode(outf), axis, perm), axis)
            return esc_decode(w)

        telemetry.emit("pipe.hops", jnp.float32(1))
        telemetry.emit("pipe.escalated", trip.astype(jnp.float32))
        # charged at the base rung: the escalated branch's width is a
        # runtime decision, so the static byte count reflects the healthy
        # path (escalations are separately visible via pipe.escalated)
        telemetry.emit(
            "pipe.hop_bytes", float(wire.size * wire.dtype.itemsize))
        return contain(jax.lax.cond(trip, widened, base)).astype(dtype)

    def body(w_local, x_all):
        # w_local leaves are [1, ...] (this stage's slice); drop the stage dim
        w = jax.tree.map(lambda a: a[0], w_local)
        p = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(nstages - 1)]
        recv = jnp.zeros(x_all.shape[1:], x_all.dtype)
        out_buf = jnp.zeros_like(x_all)
        for t in range(M + nstages - 1):
            # stage 0 injects microbatch t (clamped: for t >= M it recomputes
            # the last microbatch, whose output never reaches the final stage
            # inside the window); later stages consume the permuted wavefront
            inp = jnp.where(p == 0, x_all[min(t, M - 1)], recv)
            out = stage_fn(w, inp)
            m = t - (nstages - 1)
            if 0 <= m < M:
                # only the final stage's output is a real result; zeros from
                # the other stages vanish in the psum broadcast below
                out_buf = out_buf.at[m].set(jnp.where(p == nstages - 1, out, 0.0))
            if nstages > 1:
                telemetry.emit("pipe.ticks", jnp.float32(1))
                with jax.named_scope(f"pipe.hop.{name}"):
                    if hop_encode is None:
                        recv = plain_hop(out, perm)
                    elif guard is None:
                        recv = coded_hop(out, perm, x_all.dtype)
                    else:
                        recv = guarded_hop(out, perm, x_all.dtype)
        return jax.lax.psum(out_buf, axis)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(), check_vma=False
    )
    return fn(stage_params, x)
