"""Wire-format-compressed cross-pod collectives.

The paper's uniform-format transport argument applied to the scarcest
bandwidth in a multi-pod deployment: the inter-pod interconnect.  Gradients
(and any other reduction payload) cross the wire as packed wire-format bit
patterns instead of f32 — any registered <=16-bit
:class:`~repro.core.formats.WireFormat`: takum8/16 (4x/2x fewer bytes),
OFP8 E4M3/E5M2 (4x, the AVX10.2-zoo status quo), or bf16 (2x) — while
every arithmetic accumulation stays in f32 (accumulate-wide /
transport-narrow — the same split the VDPPT dequant kernels make for HBM).
Running takum and OFP8 through the *same* ring is what makes the paper's
wire-quality head-to-head apples-to-apples (``collectives_bench``).

Algorithm (``compressed_psum``): a P-hop ring.  Each device encodes its
local contribution once (RNE takum encode, DAZ semantics fixed in PR 1) and
the *bit patterns* circulate via ``lax.ppermute`` — re-encoding is never
needed because decode(encode(x)) is a fixed point of the codec.  Decode on
arrival is a single gather from the exact f32 decode LUT
(:mod:`repro.core.tables`), i.e. the PR-1 LUT codec applied at the wire.
After P-1 hops every device holds every source's payload; terms are
reordered into *source order* before the f32 summation so all devices reduce
in the same order and the result is bit-identical across the ring (at the
cost of one P-deep stack of the payload, fine for single-digit pod counts).

Error model: with ``exact_local=True`` (default) the device's own term is
kept in f32, so exactly P-1 terms carry one quantisation error each — the
bound the dist tests assert.  ``exact_local=False`` quantises the local term
too (every device then sums identical values; used by the train step and by
error feedback, whose residual bookkeeping needs the transmitted value).

``wire_bytes_per_element`` is the matching analytic traffic model: a P-ring
all-reduce moves P-1 messages of the full payload per device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ofp8, telemetry
from repro.core.formats import special_fraction, wire_format
from repro.core.takum import takum_encode_sr
from repro.kernels.lut import decode_jnp_fast, encode_jnp_fast
from repro.quant import blockscale

from . import faults

IS_STUB = False


def wire_codec(fmt, *, sr_key=None):
    """(encode, decode) pair moving f32 payloads through wire format ``fmt``.

    ``encode`` maps f32 -> the wire payload (packed uint bits, or bf16 for
    the bf16 wire; for the block-scaled mx* formats the interleaved
    scale+bits payload — last dim n -> n/32*33, n a 32-multiple — so the
    E8M0 scales and element bytes cross the ring as one message); ``decode``
    maps a payload back to f32.  ``sr_key`` switches the takum/OFP8 encode
    to stochastic rounding (takum: bit-string SR; OFP8: the
    truncate-plus-dither encoder — DESIGN.md §6); bf16 defines RNE only and
    the block containers derive their scales deterministically, so it is
    ignored there.  Shared by the compressed psum ring, error feedback and
    the pipeline stage hops — all of which pad/slice the last axis around
    this codec for block formats (``blockscale.pad_block``).
    """
    wf = wire_format(fmt)
    if wf.name == "f32":
        raise ValueError("f32 is the accumulate format, not a compressed wire")
    if wf.name == "bf16":
        return (
            _arm_encode(lambda v: v.astype(jnp.bfloat16), wf.name),
            lambda m: m.astype(jnp.float32),
        )
    if wf.is_block_scaled:
        # scale bytes + element bytes in one interleaved uint8 payload:
        # decode(encode(x)) is a codec fixed point here too (the conformance
        # suite's idempotence property), so the ring never re-encodes
        return (
            _arm_encode(lambda v: encode_jnp_fast(v, wf.name), wf.name),
            lambda m: decode_jnp_fast(m, wf.name),
        )
    if not wf.supports_lut_decode:
        raise ValueError(
            f"compressed wire format {wf.name!r} unsupported: the LUT decode "
            "tabulates 2**n entries (use a <=16-bit format, or f32/bf16)"
        )
    if wf.family == "takum" and sr_key is not None:
        encode = lambda v: takum_encode_sr(v, sr_key, wf.nbits)
    elif wf.family == "ofp8" and sr_key is not None:
        encode = lambda v: ofp8.encode_sr(v, sr_key, wf.name)
    else:
        # producer-side fast encode: the per-format measured winner (table
        # path for takum — bit-identical to takum_encode — short bit-twiddle
        # for OFP8), so the ring's encode stops being the heaviest op in a
        # compressed psum.  The takum encode tables are numpy-built, hence
        # safe to first-build inside eager shard_map traces.
        encode = lambda v: encode_jnp_fast(v, wf.name)
    return _arm_encode(encode, wf.name), (lambda m: decode_jnp_fast(m, wf.name))


def _arm_encode(encode, fmt_name: str):
    """Trace-time fault hook: inside a ``faults.inject`` scope with wire
    corruption enabled, encoded payloads take the configured byte/bit and
    mx-scale faults on their way out; otherwise ``encode`` is untouched
    (zero extra trace ops)."""
    cfg = faults.active()
    if cfg is None or not cfg.corrupts_wire:
        return encode
    return lambda v: faults.corrupt_payload(encode(v), fmt_name)


def axis_size(axis_name) -> int:
    """Static size of a shard_map axis (psum of 1 constant-folds to an int)."""
    return jax.lax.psum(1, axis_name)


def varying(tree, axis_name):
    """Mark every leaf of ``tree`` as varying over ``axis_name`` (leaves
    that already vary pass through).  The two arms of a ``lax.cond`` must
    agree on it, and a psum'd or constant leaf is invariant where a ring
    result varies.  Outside ``check_vma`` shard_maps this is the identity."""

    def one(x):
        x = jnp.asarray(x)
        return x if axis_name in jax.typeof(x).vma else jax.lax.pvary(x, axis_name)

    return jax.tree.map(one, tree)


def _ring_reduce(wire, own_f32, axis_name, decode, N: int,
                 canonical_order: bool = True, contain_abs=None,
                 fmt_name: str = "wire"):
    """P-1 ``ppermute`` hops of narrow wire payloads; f32 sum of the decodes.

    ``wire`` is this device's encoded contribution (takum bits or bf16),
    ``decode`` maps a payload to f32, and ``own_f32`` is the term the device
    charges itself (exact f32 or its own decode, see module docstring).
    With ``canonical_order`` the terms are gathered into *source* order
    before the reduction, so every ring member sums in the same order and
    the result is bit-identical across devices.  That gather needs
    ``lax.axis_index``, which only lowers inside *fully* manual shard_map
    regions (in partially-auto regions it becomes an XLA PartitionId, which
    SPMD cannot partition) — callers in partial-auto contexts pass False and
    accept ulp-level cross-pod divergence from the per-device hop order.

    ``contain_abs`` arms corruption containment (DESIGN.md §8): every term
    entering the reduction has its non-finite and ``|v| > contain_abs``
    elements zeroed — a flipped takum/bf16 wire byte decodes to NaR/NaN/Inf
    or an implausible ~1e38 magnitude, and one such element would otherwise
    poison the whole reduction.  Returns ``(sum, contained)`` where
    ``contained`` is this device's f32 count of zeroed elements (0.0 when
    containment is off); each hop message lands on exactly one device, so
    the per-device counts sum to the global count.

    Observability (``wire.*``, DESIGN.md §9; zero ops unless a telemetry
    capture is active at trace time): per ring call, ``wire.hops`` (N-1)
    and ``wire.hop_bytes`` (the honest per-device wire traffic, payload
    bytes x hops), plus one ``wire.hop.<fmt>`` span (cat ``collective``)
    per hop — per device, so totals carry the ring multiplicity N.
    """
    def arm(term):
        if contain_abs is None:
            return term, jnp.float32(0)
        bad = ~jnp.isfinite(term) | (jnp.abs(term) > contain_abs)
        return jnp.where(bad, jnp.float32(0), term), jnp.sum(bad, dtype=jnp.float32)

    perm = [(i, (i + 1) % N) for i in range(N)]
    own, contained = arm(own_f32)
    terms = [own]  # hop 0 = own payload = source p
    msg = wire
    if telemetry.enabled():
        msg_bytes = float(wire.size * wire.dtype.itemsize)
        telemetry.emit("wire.hops", float(N - 1))
        telemetry.emit("wire.hop_bytes", (N - 1) * msg_bytes)
    for _ in range(N - 1):
        with telemetry.trace_span(f"wire.hop.{fmt_name}", cat="collective") as sp:
            msg = faults.corrupt_hop(jax.lax.ppermute(msg, axis_name, perm), axis_name)
            term, c = arm(decode(msg))  # hop i carries source (p - i) % N
            sp.dep = telemetry.probe(term)
        contained = contained + c
        terms.append(term)
    stacked = jnp.stack(terms)
    if canonical_order:
        p = jax.lax.axis_index(axis_name)
        stacked = jnp.take(stacked, (p - jnp.arange(N)) % N, axis=0)
    return jnp.sum(stacked, axis=0), contained


def compressed_psum(x, axis_name, fmt="t8", *, exact_local: bool = True,
                    canonical_order: bool = True, sr_key=None):
    """All-reduce-sum across ``axis_name`` with wire-compressed payloads.

    Must be called inside ``shard_map`` (the axis must be a manual mesh
    axis).  ``fmt`` is any registered wire format (name, alias, WireFormat,
    or bare takum width): "f32" falls through to the native ``lax.psum``
    (exact); every <=16-bit format — t8/t16, OFP8 e4m3/e5m2, bf16 — rides
    the same narrow-wire / f32-accumulate ring (a plain bf16 psum would
    also *sum* in bf16, charging the wire format for narrow-accumulation
    error it didn't cause).  Wider formats are rejected: the LUT decode
    tabulates 2**n entries.  Overflow semantics follow the format: takum
    saturates (finite stays finite), E5M2/bf16 round to ±Inf, E4M3 rounds
    into NaN — part of what the wire-quality benches measure.  ``sr_key``
    switches the takum wire encode from RNE to stochastic rounding
    (``QuantPolicy.stochastic_rounding`` for grad_comm); fold the ring
    member's index into the key so SR noise decorrelates across sources —
    but replicas of one source (e.g. data-axis copies in a fully-manual
    region) must share a key, or their rings diverge bitwise.  (The
    IEEE/OFP8 families only define RNE; ``sr_key`` is ignored there.)
    Returns f32 of ``x``'s shape.  See :func:`_ring_reduce` for
    ``canonical_order``.
    """
    xf = x.astype(jnp.float32)
    wf = wire_format(fmt)
    if wf.name == "f32":
        return jax.lax.psum(xf, axis_name)
    N = axis_size(axis_name)
    if N == 1:
        return xf
    n = xf.shape[-1] if xf.ndim else 1
    if wf.is_block_scaled:
        # the block codec moves whole 32-blocks: zero-pad the last axis in,
        # slice back out (zero padding never perturbs a block's scale)
        xf = blockscale.pad_block(jnp.atleast_1d(xf))
    encode, decode = wire_codec(wf.name, sr_key=sr_key)
    with telemetry.trace_span(f"wire.ring.{wf.name}", cat="collective") as sp:
        wire = encode(xf)
        own = xf if exact_local else decode(wire)
        out, _ = _ring_reduce(
            wire, own, axis_name, decode, N, canonical_order, fmt_name=wf.name
        )
        if wf.is_block_scaled:
            out = out[..., :n].reshape(jnp.shape(x))
        sp.dep = telemetry.probe(out)
    telemetry.emit("wire.calls", jnp.float32(1))
    telemetry.emit(f"wire.rung.{wf.name}", jnp.float32(1))
    return out


def compressed_pmean(x, axis_name, fmt="t8", *, exact_local: bool = False,
                     canonical_order: bool = True, sr_key=None):
    """Mean-reduction variant (gradient sync).  Defaults to quantising the
    local term so ring members agree up to summation order."""
    N = axis_size(axis_name)
    return compressed_psum(
        x, axis_name, fmt, exact_local=exact_local,
        canonical_order=canonical_order, sr_key=sr_key,
    ) / N


def degraded_psum(x, axis_name, fmt, guard, *, exact_local: bool = True,
                  canonical_order: bool = True, sr_key=None):
    """Guarded all-reduce-sum: ``compressed_psum`` plus the fault guards of
    a :class:`~repro.quant.policy.GuardPolicy` (DESIGN.md §8).

    Three layers, innermost first:

    1. **input containment** — non-finite elements of the local contribution
       are zeroed (and counted) before anything touches the wire, so one
       poisoned lane cannot NaR-saturate its encode and wipe the payload.
    2. **hop containment** — arriving ring terms pass the
       ``contain_hops``/``contain_abs`` rail of :func:`_ring_reduce`.
    3. **the degradation ladder** — per rung, a *local* health check (encoded
       payload special fraction, plus the relative rms quantisation error of
       the finite lanes) is psum'd into a ring-uniform trip flag; on trip the
       hop re-runs one rung wider (``guard.ladder_from(fmt)``), with f32 =
       exact ``lax.psum`` as the unconditional last refuge.  The psum *must*
       precede the branch: a collective inside a divergent ``lax.cond`` arm
       deadlocks the ring.  Only the chosen rung's ring executes (nested
       ``lax.cond``), so the steady-state cost is one narrow ring plus one
       scalar psum per non-final rung.

    Telemetry (when a :func:`repro.core.telemetry.capture` scope is active at
    trace time): ``wire.calls``, ``wire.rung`` (chosen rung index),
    ``wire.escalated``, ``wire.rung.<fmt>`` per-rung hit counts,
    ``wire.contained`` (zeroed hop elements), ``wire.specials_in`` (poisoned
    input lanes) — all per-device, summed across the ring by the callback.
    """
    xf = x.astype(jnp.float32)
    shape = jnp.shape(x)
    n = xf.shape[-1] if xf.ndim else 1
    bad_in = ~jnp.isfinite(xf)
    n_bad = jnp.sum(bad_in, dtype=jnp.float32)
    xf = jnp.where(bad_in, jnp.float32(0), xf)
    rungs = guard.ladder_from(wire_format(fmt).name)
    N = axis_size(axis_name)
    contain = guard.contain_abs if guard.contain_hops else None

    if N == 1 or rungs == ("f32",):
        out = xf if N == 1 else jax.lax.psum(xf, axis_name)
        rung = jnp.float32(0)
        contained = jnp.float32(0)
    else:
        def attempt(i):
            wf = wire_format(rungs[i])
            if wf.name == "f32":
                telemetry.emit("wire.rung.f32", jnp.float32(1))
                return jax.lax.psum(xf, axis_name), jnp.float32(i), jnp.float32(0)
            xp = blockscale.pad_block(jnp.atleast_1d(xf)) if wf.is_block_scaled else xf
            key = sr_key if wf.family in ("takum", "ofp8") else None
            encode, decode = wire_codec(wf.name, sr_key=key)
            wire = encode(xp)
            q = decode(wire)

            def ring():
                own = xp if exact_local else q
                out, contained = _ring_reduce(
                    wire, own, axis_name, decode, N, canonical_order,
                    contain_abs=contain, fmt_name=wf.name)
                if wf.is_block_scaled:
                    out = out[..., :n].reshape(shape)
                telemetry.emit(f"wire.rung.{wf.name}", jnp.float32(1))
                return out, jnp.float32(i), contained

            if i == len(rungs) - 1:
                return ring()  # last rung: no refuge left, send regardless
            spec = special_fraction(wire, wf.name)
            fin = jnp.isfinite(q)
            err = jnp.where(fin, q - xp, jnp.float32(0))
            rel = jnp.sqrt(jnp.mean(jnp.square(err))) / (
                jnp.sqrt(jnp.mean(jnp.square(xp))) + jnp.float32(1e-12))
            trip_local = (spec > guard.max_special_frac) | (rel > guard.max_rel_err)
            # uniform trip decision BEFORE the branch (see docstring)
            trip = jax.lax.psum(trip_local.astype(jnp.float32), axis_name) > 0
            return jax.lax.cond(
                trip,
                lambda: varying(attempt(i + 1), axis_name),
                lambda: varying(ring(), axis_name),
            )

        out, rung, contained = attempt(0)

    telemetry.emit("wire.calls", jnp.float32(1))
    telemetry.emit("wire.rung", rung)
    telemetry.emit("wire.escalated", (rung > 0).astype(jnp.float32))
    telemetry.emit("wire.contained", contained)
    telemetry.emit("wire.specials_in", n_bad)
    return out


def degraded_pmean(x, axis_name, fmt, guard, *, exact_local: bool = False,
                   canonical_order: bool = True, sr_key=None):
    """Guarded mean-reduction (gradient sync under a GuardPolicy)."""
    N = axis_size(axis_name)
    return degraded_psum(
        x, axis_name, fmt, guard, exact_local=exact_local,
        canonical_order=canonical_order, sr_key=sr_key,
    ) / N


def wire_bytes_per_element(fmt, pods: int) -> float:
    """Bytes per payload element crossing the wire on a ``pods``-wide ring.

    A P-ring all-reduce sends P-1 full-payload messages per device; each
    element travels as a ``fmt`` bit pattern *plus its share of any
    container overhead* — the block-scaled formats add one E8M0 scale byte
    per 32-block, i.e. 8.25 bits/element (``WireFormat.wire_bits_per_el``).
    f32 -> t16/bf16 halves the wire, f32 -> t8/e4m3/e5m2 quarters it, and
    f32 -> mx* is a 3.88x cut, independent of P.
    """
    return (pods - 1) * wire_format(fmt).wire_bits_per_el / 8
