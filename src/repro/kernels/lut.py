"""Kernel-safe table-driven wire codecs (gather-based decode/encode).

The alternative to the branch-free bit-twiddle decoders: a single VMEM
gather per element from the precomputed tables in :mod:`repro.core.tables`.
The gather kernel is *format-agnostic* — the same `jnp.take` serves takum8,
E4M3, E5M2 and bf16; only the table operand changes — which is what lets
every kernel hot path (matmul, dual-matmul, decode-attention, 2D codec)
accept any registered :class:`~repro.core.formats.WireFormat` through one
``decode_impl={"bits", "lut"}`` knob.  "bits" dispatches to the format
family's branch-free decoder (takum bit-assembly, OFP8 field unpack, bf16
shift-bitcast); "lut" gathers.  Per-format, per-op defaults live in
``DEFAULT_DECODE_IMPL`` (LUT for the 8-bit formats — 1 KiB tables — and
bits for the 16-bit ones, whose 256 KiB tables occupy a meaningful VMEM
fraction; the A/B switch is the point) and ``DEFAULT_ENCODE_IMPL`` (the
measured encode winners differ — see that table's comment).

Tables enter kernels as ordinary pallas_call operands with a whole-array
BlockSpec, shaped ``(2**n // 128, 128)`` so they tile cleanly into VMEM
lanes; the kernel body flattens and gathers.  See DESIGN.md §3 for the
bit-twiddle-vs-LUT trade-off discussion.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import wire_format
from repro.core.tables import (
    ENC8_THR_FLAG,
    decode_table_f32,
    encode_tables,
    ofp8_overflow_code,
)
from repro.quant import blockscale
from .common import decode_takum_f32, encode_takum_from_f32

_U = jnp.uint32

#: per-format default decode implementation (the A/B knob's resting position)
DEFAULT_DECODE_IMPL = {
    "t8": "lut",
    "t16": "bits",
    "e4m3": "lut",
    "e5m2": "lut",
    "bf16": "bits",
}
#: per-format default *encode* implementation.  Decode and encode winners
#: differ.  Takum: the bit-twiddle encode is the heaviest codec body in the
#: stack (~40 integer ops incl. a popcount regime scan), so the table path
#: wins in *both* bench modes — op-dispatch (the instruction-count/TPU
#: proxy) by 3-8x and XLA-fused consistently across rounds (t8 ~1.3-1.4x,
#: t16 ~1.1-1.3x; BENCH_kernels.json encode rows) — lut for t8 AND t16.
#: OFP8: the field packers are ~15 short ops, and in the fused mode the two
#: extra gathers buy no consistent win — the A/B hovers inside the
#: container's ~+-20% noise with bits ahead in most measurement rounds,
#: including the PR 3 baseline that exposed the old "8-bit defaults to
#: LUT" rule as wrong for OFP8 encode (e4m3 bits 2663 vs lut 2174 Melem/s,
#: e5m2 2296 vs 2112) — so e4m3/e5m2 default to bits, which also keeps
#: their 2 KiB encode tables out of VMEM.  bf16 encode is a 2-op
#: shift-round: bits, untabulated.
DEFAULT_ENCODE_IMPL = {
    "t8": "lut",
    "t16": "lut",
    "e4m3": "bits",
    "e5m2": "bits",
    "bf16": "bits",
}
#: supported values for the decode_impl/encode_impl knobs
DECODE_IMPLS = ("bits", "lut")


def resolve_impl(impl: str | None, fmt, op: str = "decode") -> str:
    """None -> per-format default; otherwise validate the explicit choice.

    ``op`` selects the default table ("decode" or "encode") and the
    tabulability check — decode tables exist for every <=16-bit format,
    encode tables for the 8-bit formats and takum16.

    Block-scaled formats resolve against their *element* format: the impl
    knob selects the element codec inside the container (the E8M0 scale
    path is the same handful of integer ops either way), so e.g. mxt8
    defaults to the takum8 LUTs and mxe4m3 decode to the e4m3 LUT.
    """
    assert op in ("decode", "encode"), op
    wf = wire_format(fmt)
    if wf.is_block_scaled:
        return resolve_impl(impl, wf.elem_name, op)
    if wf.family == "takum" and wf.nbits > 16:
        # the kernel codec bodies are only valid for narrow takums (the
        # branch-free encode needs rounding shift 28 + r - n >= 0, the f32
        # decode needs p <= 23): reject t32 loudly instead of silently
        # corrupting bits — wide takums go through the registry codec
        # (ref.codec_*_ref / wf.encode_jnp), not the Pallas kernels
        raise ValueError(
            f"kernel codecs support <=16-bit takums, got {wf.name!r}; "
            "use the jnp reference path"
        )
    defaults = DEFAULT_DECODE_IMPL if op == "decode" else DEFAULT_ENCODE_IMPL
    if impl is None:
        return defaults.get(wf.name, "bits")
    if impl not in DECODE_IMPLS:
        raise ValueError(f"{op}_impl must be one of {DECODE_IMPLS}, got {impl!r}")
    tabulable = wf.supports_lut_decode if op == "decode" else wf.supports_lut_encode
    if impl == "lut" and not tabulable:
        raise ValueError(f"{op}_impl='lut': no tables for {wf.name} ({wf.nbits}b)")
    if (
        impl == "lut"
        and op == "decode"
        and (1 << wf.nbits) > LANE_GATHER_MAX_ROWS * 128
        and jax.default_backend() == "tpu"
    ):
        raise ValueError(
            f"decode_impl='lut' for {wf.name}: its {1 << wf.nbits}-entry table "
            f"cannot be gathered on TPU (Mosaic gathers within one 128-lane "
            f"vreg; tables up to {LANE_GATHER_MAX_ROWS * 128} entries are "
            f"gathered row by row) — use decode_impl='bits'"
        )
    return impl


def decode_bits_fn(fmt):
    """The format's kernel-safe branch-free decode: uint bits -> float32.

    Takum keeps the dedicated bit-assembly decoder in :mod:`.common`
    (bit-identical to the LUT by construction); the other families use the
    registry's unjitted ``decode_jnp`` (pure jnp ops, pallas-traceable).
    Block-scaled formats wrap the *element* decode with the payload
    unpack + E8M0 scale multiply (see :func:`wire_decode_fn` for the
    kernel-facing closure that also covers the LUT impl).
    """
    wf = wire_format(fmt)
    if wf.is_block_scaled:
        elem_dec = decode_bits_fn(wf.elem_name)
        return lambda payload: blockscale.decode_payload(
            payload, wf, elem_decode=elem_dec
        )
    if wf.family == "takum":
        return lambda bits: decode_takum_f32(bits, wf.nbits)
    return wf.decode_jnp


def encode_bits_fn(fmt):
    """The format's kernel-safe branch-free encode: float32 -> uint bits."""
    wf = wire_format(fmt)
    if wf.is_block_scaled:
        elem_enc = encode_bits_fn(wf.elem_name)
        return lambda x: blockscale.encode_payload(x, wf, elem_encode=elem_enc)
    if wf.family == "takum":
        return lambda x: encode_takum_from_f32(x, wf.nbits)
    return wf.encode_jnp


def wire_decode_fn(fmt, impl, tab_ref=None):
    """The tile-decode closure a kernel body applies to its VMEM input tile.

    ``impl == "lut"`` gathers from ``tab_ref`` (the decode-table operand ref
    for the format — the *element* format's table for block-scaled
    containers); ``"bits"`` is the branch-free family decode.  For
    block-scaled formats the closure takes the split operand form
    (:func:`repro.quant.blockscale.split_payload`): ``(bits, sb)`` tiles of
    element bits and per-element scale bytes, both ``[..., n]``.
    """
    wf = wire_format(fmt)
    if impl == "lut":
        inner = lambda bits: decode_wire_lut(tab_ref[...], bits)
    else:
        inner = decode_bits_fn(wf.elem_name if wf.is_block_scaled else wf.name)
    if wf.is_block_scaled:
        return lambda bits, sb: blockscale.dequantize_elems(
            bits, sb, wf, elem_decode=inner
        )
    return inner


def wire_encode_fn(fmt, impl, enc_tab_refs=()):
    """The tile-encode closure of the codec kernel: f32 tile -> uint codes.

    ``enc_tab_refs`` are the LUT operand refs (empty for the bits impl).
    For block-scaled formats the closure takes ``(x, sb)`` — the tile and
    its per-element scale bytes, derived by XLA around the kernel — and
    returns element bits (the split form, see :func:`wire_decode_fn`).
    """
    wf = wire_format(fmt)
    name = wf.elem_name if wf.is_block_scaled else wf.name
    if impl == "lut":
        inner = lambda v: encode_wire_lut(v, tuple(t[...] for t in enc_tab_refs), name)
    else:
        inner = encode_bits_fn(name)
    if wf.is_block_scaled:
        return lambda x, sb: blockscale.quantize_elems(x, sb, wf, elem_encode=inner)
    return inner


def decode_table_operand(fmt):
    """The format's decode table as a 2D f32 operand, lanes-major (the
    element format's table for block-scaled containers)."""
    wf = wire_format(fmt)
    name = wf.elem_name if wf.is_block_scaled else wf.name
    return jnp.asarray(decode_table_f32(name)).reshape(-1, 128)


def encode8_table_operands(fmt="t8"):
    """(meta, thr) 8-bit encode-table operands (back-compat PR-1 name)."""
    return encode_table_operands(fmt)


def encode_table_operands(fmt):
    """The format's LUT-encode tables as a tuple of 2D lanes-major operands:
    (meta, thr) for the 8-bit formats, (meta, sub) for takum16 — consumed
    positionally by :func:`encode_wire_lut`.  Block-scaled containers use
    their element format's tables."""
    wf = wire_format(fmt)
    name = wf.elem_name if wf.is_block_scaled else wf.name
    return tuple(jnp.asarray(t).reshape(-1, 128) for t in encode_tables(name))


#: tables of at most this many 128-lane rows are gathered lane-wise (the
#: 256-entry byte-indexed tables); larger ones (the 65536-entry takum16
#: decode table) only through ``jnp.take``, which Mosaic cannot lower
LANE_GATHER_MAX_ROWS = 2


def table_lookup(tab, idx):
    """Per-element table gather ``tab.flat[idx]`` in a form Mosaic lowers.

    A 1-D ``tab`` (the XLA-side jnp codecs) is a plain ``jnp.take``.  A
    lanes-major ``(rows, 128)`` operand (the in-kernel form) with at most
    :data:`LANE_GATHER_MAX_ROWS` rows is gathered one 128-lane row at a
    time: Mosaic only lowers a 2-D ``take_along_axis`` whose source is a
    single vreg along the gathered axis, so each table row is broadcast
    over the index tile (viewed as ``[-1, 128]``), gathered by the low 7
    index bits and selected by the high bits.  ``idx`` tiles whose last dim
    is not a multiple of 128 (interpret-mode only) fall back to the take.
    """
    idx = idx.astype(jnp.int32)
    if (
        tab.ndim != 2
        or tab.shape[0] > LANE_GATHER_MAX_ROWS
        or idx.ndim == 0
        or idx.shape[-1] % 128
    ):
        return jnp.take(tab.reshape(-1), idx, axis=0)
    i2 = idx.reshape(-1, 128)
    lane, row = i2 & 127, i2 >> 7
    out = None
    for r in range(tab.shape[0]):
        src = jnp.broadcast_to(tab[r : r + 1, :], i2.shape)
        g = jnp.take_along_axis(src, lane, axis=1)
        out = g if out is None else jnp.where(row == r, g, out)
    return out.reshape(idx.shape)


def decode_wire_lut(tab, bits):
    """Gather-based wire decode: uint patterns -> float32 values.

    ``tab`` is the f32 decode table for the same format as ``bits`` (1-D,
    or the ``(rows, 128)`` kernel operand); the mapping is a pure
    per-element gather — zero, NaR/NaN/Inf and negative patterns are all
    just table rows.
    """
    return table_lookup(tab, bits)


#: back-compat alias (PR-1 name; the gather was never takum-specific)
decode_takum_lut = decode_wire_lut


def _shift_round_rne(base, s, m23):
    """The shift-path rounding core: ``base + RNE(m23 >> s)`` with ties to
    the even *code*; the carry across binades is exact because both takum
    codes and IEEE/OFP8 magnitude codes are consecutive integers in value
    order.  All operands uint32 — the single copy of the tie-to-even logic,
    shared by the 8-bit exponent-byte tail and the two-level takum16 tail.
    """
    kept = m23 >> s
    guard = (m23 >> (s - 1)) & 1
    below = m23 & ((_U(1) << (s - 1)) - 1)
    rnd = (guard == 1) & ((below != 0) | (((base + kept) & 1) == 1))
    return base + kept + rnd.astype(_U)


def _round_shift_or_threshold(m23, mt, t):
    """Shared 8-bit encode tail: exponent-byte table entry -> magnitude code.

    Threshold path: the binade holds at most one rounding boundary.  Shift
    path: :func:`_shift_round_rne`.
    """
    base = mt >> 8
    s = mt & _U(0x7F)
    mag_t = base + (m23 > t).astype(_U)
    mag_s = _shift_round_rne(base, s, m23.astype(_U))
    return jnp.where((mt & _U(ENC8_THR_FLAG)) != 0, mag_t, mag_s)


def encode_takum8_lut(x, meta, thr):
    """LUT-assisted exact f32 -> takum8 encode (two gathers + integer tail).

    Bit-identical to ``takum.takum_encode(x, 8, mode="linear")``: RNE on the
    bit string with ties to even, two's-complement negatives, NaR for
    inf/NaN, and DAZ (f32 subnormals encode to 0).  ``meta``/``thr`` come
    from :func:`encode8_table_operands`.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), _U)
    neg = bits >> 31
    a = bits & _U(0x7FFFFFFF)
    is_nar = a >= _U(0x7F800000)

    e = (a >> 23).astype(jnp.int32)
    m23 = (a & _U(0x7FFFFF)).astype(jnp.int32)
    mt = table_lookup(meta, e)
    t = table_lookup(thr, e)

    mag = _round_shift_or_threshold(m23, mt, t)
    enc = jnp.where(neg == 1, (_U(0) - mag) & _U(0xFF), mag)
    enc = jnp.where(is_nar, _U(0x80), enc)
    return enc


def encode_ofp8_lut(x, meta, thr, fmt: str):
    """LUT-assisted exact f32 -> OFP8 encode (sign-magnitude tail).

    Bit-identical to ``ofp8.encode(x, fmt)`` / ml_dtypes RNE: the shared
    gather+round core, then the sign bit is OR'd on and rounding past the
    top finite code is capped at the format's overflow pattern (E4M3 NaN /
    E5M2 Inf — the round-as-if-unbounded-then-replace OCP rule).
    """
    ovf = _U(ofp8_overflow_code(fmt))
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), _U)
    sign = bits >> 31
    a = bits & _U(0x7FFFFFFF)
    is_inf = a == _U(0x7F800000)
    is_nan = a > _U(0x7F800000)

    e = (a >> 23).astype(jnp.int32)
    m23 = (a & _U(0x7FFFFF)).astype(jnp.int32)
    mt = table_lookup(meta, e)
    t = table_lookup(thr, e)

    mag = _round_shift_or_threshold(m23, mt, t)
    # top-binade carry past the last finite code (signed: Mosaic has no minui)
    mag = jnp.minimum(mag.astype(jnp.int32), jnp.int32(ofp8_overflow_code(fmt))).astype(_U)
    mag = jnp.where(is_inf, ovf, mag)  # E4M3: Inf -> NaN (ovf *is* the NaN)
    mag = jnp.where(is_nan, _U(0x7F), mag)
    return ((sign << 7) | mag).astype(_U)


def encode_wire8_lut(x, meta, thr, fmt):
    """Dispatch the 8-bit LUT encode tail by format family."""
    wf = wire_format(fmt)
    if wf.family == "takum":
        return encode_takum8_lut(x, meta, thr)
    if wf.family == "ofp8":
        return encode_ofp8_lut(x, meta, thr, wf.name)
    raise ValueError(f"no LUT encode for family {wf.family!r}")


def encode_takum16_lut(x, meta, sub):
    """Two-level LUT exact f32 -> takum16 encode (two gathers + integer tail).

    Bit-identical to ``takum.takum_encode(x, 16, mode="linear")``: gather 1
    maps the f32 exponent byte to ``(base << 8) | r`` (binade-bottom code +
    regime), gather 2 maps the regime to its mantissa shift, then the shared
    RNE tail rounds with ties to the even *code* — the mantissa-overflow
    carry crosses binades exactly because takum codes are consecutive
    integers in value order.  No threshold path exists (takum16 keeps
    p = 11 - r >= 4 mantissa bits in every f32-reachable binade) and no
    saturation clamp is needed (|c| <= 128 after carry, far from the +-255
    takum16 rails).  DAZ (f32 subnormals -> 0) and NaR are explicit.
    ``meta``/``sub`` come from :func:`encode_table_operands`.
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), _U)
    neg = bits >> 31
    a = bits & _U(0x7FFFFFFF)
    is_nar = a >= _U(0x7F800000)
    is_zero = a < _U(0x00800000)  # zero + f32 subnormals (DAZ)

    e = (a >> 23).astype(jnp.int32)
    m23 = a & _U(0x7FFFFF)
    mt = table_lookup(meta, e)
    base = mt >> 8
    s = table_lookup(sub, mt & _U(0xFF)).astype(_U)
    mag = _shift_round_rne(base, s, m23)

    enc = jnp.where(neg == 1, (_U(0) - mag) & _U(0xFFFF), mag)
    enc = jnp.where(is_zero, _U(0), enc)
    enc = jnp.where(is_nar, _U(0x8000), enc)
    return enc


def encode_wire_lut(x, tabs, fmt):
    """Generic table-driven encode: dispatch on the format's table scheme.

    ``tabs`` is the operand tuple from :func:`encode_table_operands` —
    (meta, thr) for the 8-bit exponent-byte scheme, (meta, sub) for the
    takum16 two-level scheme.
    """
    wf = wire_format(fmt)
    if wf.nbits == 8:
        return encode_wire8_lut(x, tabs[0], tabs[1], wf.name)
    if wf.name == "t16":
        return encode_takum16_lut(x, tabs[0], tabs[1])
    raise ValueError(f"no LUT encode for {wf.name!r}")


# ---------------------------------------------------------------------------
# fused encode epilogues (shared by matmul, dual-matmul, decode-attention)
# ---------------------------------------------------------------------------


def resolve_out_fmt(out_fmt, encode_impl):
    """Normalise a producer kernel's fused-encode knobs.

    Returns ``(canonical_name, impl)``, or ``(None, None)`` for a plain f32
    output.  The shared front half of every ``out_fmt=`` entry point.
    """
    if out_fmt is None:
        return None, None
    name = wire_format(out_fmt).name
    return name, resolve_impl(encode_impl, name, op="encode")


def encode_epilogue(out_fmt, out_impl, enc_tab_refs):
    """The in-register wire-encode tail a producer kernel applies to its f32
    output tile right before the HBM store (the fused-encode contract: the
    epilogue encodes exactly the f32 values the unfused kernel would have
    written, so fused == encode(unfused) bit-for-bit).  Returns f32 tile ->
    uint code tile; ``enc_tab_refs`` are the LUT operand refs (empty for the
    bits impl).  For a block-scaled ``out_fmt`` the epilogue derives the
    per-32-block E8M0 scales from the accumulator tile and stores the
    interleaved payload — the tile's N/d extent must be a multiple of 32 so
    blocks never straddle tiles, which keeps per-tile encode identical to
    whole-array encode (tiles are 128-aligned, so this always holds)."""
    wf = wire_format(out_fmt)
    if wf.is_block_scaled:
        elem_enc = wire_encode_fn(wf.elem_name, out_impl, enc_tab_refs)
        # the cap-clip inside block_quantize runs before elem_enc, so the
        # non-saturating LUT/bit element encoders are exact here.  The
        # payload is interleaved in-kernel, which only the interpreter
        # lowers (Mosaic cannot split lanes into 33-byte groups)
        return lambda acc: blockscale.encode_payload(acc, wf, elem_encode=elem_enc)
    return wire_encode_fn(out_fmt, out_impl, enc_tab_refs)


def encode_epilogue_operands(out_fmt, out_impl):
    """The extra pallas operands the epilogue needs (LUT tables, or none)."""
    if out_fmt is not None and out_impl == "lut":
        return encode_table_operands(out_fmt)
    return ()


def jnp_decode_fn(fmt, impl=None):
    """A trace-safe jnp decode closure honouring the impl knob — the
    outside-kernels sibling of :func:`wire_decode_fn` (tables captured as
    jnp constants, so build it *outside* any traced region; inside traces
    use :func:`decode_jnp_fast`, which re-wraps per call).  Used by the
    bench harness to A/B both impls for every format, block-scaled included.
    """
    wf = wire_format(fmt)
    impl = resolve_impl(impl, wf.name)
    if impl == "bits":
        return decode_bits_fn(wf.name)
    tab = jnp.asarray(
        decode_table_f32(wf.elem_name if wf.is_block_scaled else wf.name)
    )
    inner = lambda b: decode_wire_lut(tab, b)
    if wf.is_block_scaled:
        return lambda p: blockscale.decode_payload(p, wf, elem_decode=inner)
    return inner


def jnp_encode_fn(fmt, impl=None):
    """Trace-safe jnp encode closure honouring the impl knob (see
    :func:`jnp_decode_fn` for the capture caveat)."""
    wf = wire_format(fmt)
    impl = resolve_impl(impl, wf.name, op="encode")
    if impl == "bits":
        return encode_bits_fn(wf.name)
    if wf.is_block_scaled:
        tabs = encode_table_operands(wf.name)
        inner = lambda v: encode_wire_lut(v, tabs, wf.elem_name)
        return lambda x: blockscale.encode_payload(x, wf, elem_encode=inner)
    tabs = encode_table_operands(wf.name)
    return lambda x: encode_wire_lut(x, tabs, wf.name)


# ---------------------------------------------------------------------------
# trace-safe fast jnp codecs (the producer-side encode path outside kernels)
# ---------------------------------------------------------------------------


def encode_jnp_fast(x, fmt):
    """f32 -> packed wire bits via the format's *measured-winner* encode impl.

    Pure jnp — safe inside jit, scan bodies and shard_map regions (unlike a
    pallas call) — and bit-identical to ``takum_encode`` / ``encode_jnp`` by
    the exhaustive table tests.  Takum formats take the table path (two
    gathers + integer tail beats the ~40-op popcount bit-twiddle:
    ``DEFAULT_ENCODE_IMPL``); OFP8/bf16 keep their short branch-free
    packers.  The takum encode tables are numpy-built (no jax in the
    builder), so first use inside an eager shard_map trace is safe; the
    ``jnp.asarray`` wrap happens per call on purpose — a jnp constant
    materialised inside a traced region must never outlive its trace.
    """
    wf = wire_format(fmt)
    xf = x.astype(jnp.float32)
    if wf.is_block_scaled:
        # the container around the element format's own measured winner;
        # block_quantize cap-clips before the element encode, so the
        # non-saturating fast encoders are exact here
        return blockscale.encode_payload(
            xf, wf, elem_encode=lambda v: encode_jnp_fast(v, wf.elem_name)
        )
    # supports_lut_encode first: wide takums must not reach resolve_impl
    # (which rejects them for the kernel paths) — they short-circuit to the
    # registry codec below
    if wf.supports_lut_encode and resolve_impl(None, wf.name, op="encode") == "lut":
        tabs = tuple(jnp.asarray(t) for t in encode_tables(wf.name))
        return encode_wire_lut(xf, tabs, wf.name).astype(wf.storage)
    # registry codec, NOT encode_bits_fn: the kernel bit-twiddle encoder is
    # only valid for n <= 28 (its rounding shift t = 28 + r - n must be
    # >= 0), while wf.encode_jnp is correct for every registered width —
    # t32 QTensors/KV caches must keep the exact takum_encode path
    return wf.encode_jnp(xf).astype(wf.storage)


def decode_jnp_fast(bits, fmt):
    """Packed wire bits -> f32 with kernel clamp semantics, one LUT gather
    for the tabulated formats (bf16 keeps its 2-op shift-bitcast).  The jnp
    sibling of ``decode_wire_lut``; same per-call ``jnp.asarray`` rule as
    :func:`encode_jnp_fast`.
    """
    wf = wire_format(fmt)
    if wf.is_block_scaled:
        return blockscale.decode_payload(
            bits, wf, elem_decode=lambda b: decode_jnp_fast(b, wf.elem_name)
        )
    if wf.supports_lut_decode and wf.name != "bf16":
        return decode_wire_lut(jnp.asarray(decode_table_f32(wf.name)), bits)
    if wf.family == "takum" and wf.nbits > 28:
        # the branch-free f32 bit-assembly decoder needs p <= 23 (n <= 28):
        # wide takums use the registry's exact value decoder, mirroring
        # encode_jnp_fast's registry-codec fallback
        return wf.decode_jnp(bits)
    return decode_bits_fn(wf.name)(bits)
