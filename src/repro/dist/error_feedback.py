"""Error-feedback (EF) compressed reduction.

Plain compressed psum commits one quantisation error per contribution per
step; accumulated over T steps the error random-walks as ~sqrt(T).  Error
feedback carries each worker's quantisation residual into its next
contribution:

    c_t   = g_t + e_{t-1}          (gradient + carried residual)
    q_t   = Q(c_t)                 (takum encode -> the transmitted value)
    e_t   = c_t - q_t              (new residual, stays local)
    out_t = ring_sum_j q_t^(j)     (compressed psum of the q's)

The per-step sums telescope: sum_t out_t = exact total - sum_j e_T^(j), so
the *accumulated* error is bounded by the final residuals instead of growing
with T — this is what lets takum8 gradient transport train at the
uncompressed rate (beyond-paper lever; see DESIGN.md §7).

The local term entering the ring is the *quantised* value ``q_t`` (not the
exact f32): the residual bookkeeping must charge the worker exactly what the
rest of the ring received.

Any registered lossy wire format works (takum t8/t16, OFP8 e4m3/e5m2, bf16
— the residual carry is format-agnostic), which is what lets the benches
compare EF-takum8 against EF-E4M3 gradient rings on identical machinery.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.core.formats import special_fraction, wire_format
from repro.quant import blockscale

from .collectives import _ring_reduce, axis_size, varying, wire_codec

IS_STUB = False


def ef_init(params):
    """Per-leaf f32 error accumulator pytree, zero-initialised.

    ``zeros_like`` keeps each leaf's varying mesh axes, so a state built
    inside ``shard_map`` from per-device gradients has the type the EF step
    returns — a ``lax.scan`` carry must keep its type across iterations.
    """
    return jax.tree.map(lambda a: jnp.zeros_like(a, dtype=jnp.float32), params)


def ef_compressed_psum(g, err, axis_name, fmt="t8", guard=None):
    """Compressed psum with error feedback; returns ``(reduced, new_err)``.

    ``g`` and ``err`` are matching pytrees (or single arrays); must be called
    inside ``shard_map`` over ``axis_name``.  ``reduced`` sums the
    residual-corrected, quantised contributions of every ring member in f32.
    ``fmt`` is any registered lossy wire format (f32 would make the
    residuals identically zero and is rejected by :func:`wire_codec`).

    With a :class:`~repro.quant.policy.GuardPolicy` the reduction takes the
    fault guards of ``collectives.degraded_psum`` — input containment of
    non-finite ``g + err`` lanes, the hop-containment rail, and the
    format-degradation ladder — with one EF-specific rule (DESIGN.md §8):
    **the residual is always computed against the format actually
    transmitted**.  Each ladder rung re-encodes ``c`` at its own width and
    the chosen rung's branch computes ``new_err = c - decode(encode_r(c))``;
    the f32 refuge rung transmits exactly and returns a *zero* residual.
    Carrying a t8-sized residual across a hop that actually went out as bf16
    would silently double-correct next step.
    """
    wf = wire_format(fmt)
    encode, decode = wire_codec(wf.name)  # also rejects fmt='f32' loudly
    N = axis_size(axis_name)
    rungs = (wf.name,) if guard is None else guard.ladder_from(wf.name)
    contain = None
    if guard is not None and guard.contain_hops:
        contain = guard.contain_abs

    def one(gl, el):
        c = gl.astype(jnp.float32) + el
        n = c.shape[-1] if c.ndim else 1
        shape = jnp.shape(gl)
        if guard is None:
            if wf.is_block_scaled:
                # block codec moves whole 32-blocks; the zero padding carries
                # zero residual (it encodes and decodes exactly), so the EF
                # telescoping is untouched by the pad/slice
                c = blockscale.pad_block(jnp.atleast_1d(c))
            bits = encode(c)
            q = decode(bits)
            new_err = c - q
            if N == 1:
                reduced = q
            else:
                reduced, _ = _ring_reduce(
                    bits, q, axis_name, decode, N, fmt_name=wf.name
                )
            telemetry.emit("ef.calls", jnp.float32(1))
            if wf.is_block_scaled:
                reduced = reduced[..., :n].reshape(shape)
                new_err = new_err[..., :n].reshape(shape)
            return reduced, new_err

        bad = ~jnp.isfinite(c)
        n_bad = jnp.sum(bad, dtype=jnp.float32)
        c = jnp.where(bad, jnp.float32(0), c)

        def at_rung(i):
            rwf = wire_format(rungs[i])
            if rwf.name == "f32":
                # exact transmission: the residual telescopes to nothing
                reduced = c if N == 1 else jax.lax.psum(c, axis_name)
                telemetry.emit("ef.rung.f32", jnp.float32(1))
                return reduced, jnp.zeros_like(c), jnp.float32(i), jnp.float32(0)
            cp = blockscale.pad_block(jnp.atleast_1d(c)) if rwf.is_block_scaled else c
            enc, dec = wire_codec(rwf.name)
            bits = enc(cp)
            q = dec(bits)

            def send():
                new_err = cp - q  # residual vs the format actually sent
                if N == 1:
                    reduced, contained_ = q, jnp.float32(0)
                else:
                    reduced, contained_ = _ring_reduce(
                        bits, q, axis_name, dec, N, contain_abs=contain,
                        fmt_name=rwf.name)
                if rwf.is_block_scaled:
                    out = reduced[..., :n].reshape(shape)
                    ne = new_err[..., :n].reshape(shape)
                else:
                    out, ne = reduced, new_err
                telemetry.emit(f"ef.rung.{rwf.name}", jnp.float32(1))
                return out, ne, jnp.float32(i), contained_

            if i == len(rungs) - 1:
                return send()
            spec = special_fraction(bits, rwf.name)
            fin = jnp.isfinite(q)
            errq = jnp.where(fin, q - cp, jnp.float32(0))
            rel = jnp.sqrt(jnp.mean(jnp.square(errq))) / (
                jnp.sqrt(jnp.mean(jnp.square(cp))) + jnp.float32(1e-12))
            trip_local = (spec > guard.max_special_frac) | (rel > guard.max_rel_err)
            # ring-uniform escalation: psum the trip BEFORE branching
            trip = jax.lax.psum(trip_local.astype(jnp.float32), axis_name) > 0
            return jax.lax.cond(
                trip,
                lambda: varying(at_rung(i + 1), axis_name),
                lambda: varying(send(), axis_name),
            )

        reduced, new_err, rung, contained_ = at_rung(0)
        telemetry.emit("ef.calls", jnp.float32(1))
        telemetry.emit("ef.rung", rung)
        telemetry.emit("ef.escalated", (rung > 0).astype(jnp.float32))
        telemetry.emit("ef.contained", contained_)
        telemetry.emit("ef.specials_in", n_bad)
        return reduced, new_err

    flat_g, treedef = jax.tree.flatten(g)
    flat_e = treedef.flatten_up_to(err)
    pairs = [one(gl, el) for gl, el in zip(flat_g, flat_e)]
    reduced = jax.tree.unflatten(treedef, [r for r, _ in pairs])
    new_err = jax.tree.unflatten(treedef, [e for _, e in pairs])
    return reduced, new_err
