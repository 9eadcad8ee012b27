"""OCP-Microscaling-style block scaling: shared E8M0 scales over 32-blocks.

The industry's answer to OFP8's narrow dynamic range is not a new element
format but a *container*: OCP MX ("Microscaling") groups elements into
blocks of 32 and attaches one shared power-of-two scale per block, stored
as an E8M0 byte (8 exponent bits, no sign, no mantissa).  This module is
that container for any registered 8-bit element
:class:`~repro.core.formats.WireFormat` — ``mxe4m3``/``mxe5m2`` are the OCP
MXFP8 formats, ``mxt8`` is the same container around takum8 (the paper's
head-to-head needs takum measured against the block-scaled zoo, not only
the flat one).

Semantics (OCP MX v1.0, with every deviation documented):

* **Scale derivation** (absmax): per 32-block,
  ``shared_exp = floor(log2(max|x|)) - emax_elem`` with ``emax_elem`` the
  exponent of the element format's largest binade (e4m3: 8, e5m2: 15,
  takum8: 0 — the scale drops the block's absmax into [1, 2), takum's
  maximal-precision binade).  The E8M0 byte is ``shared_exp + 127``.
* **E8M0 range**: bytes 1..254 encode scales 2^-126..2^127; byte 255 is the
  NaN scale; byte 0 (2^-127, an f32 subnormal) is *never emitted* and
  decodes clamped to 2^-126 — this stack is DAZ/FTZ end to end (DESIGN.md
  §3), so a subnormal scale is unrepresentable downstream anyway.
* **All-zero blocks** (absmax == 0, incl. all-f32-subnormal blocks under
  DAZ): scale byte 127 (scale 1.0), element bits all zero.  OCP leaves this
  choice to the implementation; 1.0 keeps the block exactly zero and the
  byte self-documenting.
* **NaN blocks**: any Inf/NaN element makes the block absmax non-finite ->
  scale byte 255 and element bits forced to 0; decode returns NaN for every
  element of the block (the OCP block-NaN rule).  Individual special values
  do not survive the container — measured, not hidden, like every other
  special-value semantic in this repo.
* **Element conversion saturates to the top of the scaled binade**: scaled
  elements are clamped to the element format's largest value below
  ``2^(emax_elem + 1)`` before the RNE encode.  For e4m3/e5m2 this *is*
  OCP's saturating conversion (clamp at 448 / 57344).  For takum8 — whose
  range extends far past the binade — the same clamp (at 1.875) keeps the
  E8M0 scale a fixed point of re-encoding: without it an absmax in
  (1.9375, 2) rounds up to 2.0 and the next encode shifts the whole block's
  scale, re-rounding every element at the coarser taper.  With the clamp,
  ``encode . decode . encode == encode`` bit-for-bit (the conformance
  suite's idempotence property).

**Wire payload**: one uint8 buffer, the scale byte riding *interleaved*
next to its 32 element bytes — ``[s0 e0..e31 s1 e32..e63 ...]`` along the
last axis, 33 bytes per block (8.25 bits/element; see
``WireFormat.wire_bits_per_el``).  Interleaving is what lets a Pallas
kernel fetch a [rows, bn] element tile *and* its scales as one contiguous
[rows, bn//32*33] VMEM block (the decode prologue / fused-encode epilogue
in the matmul/attention kernels), and makes the payload self-describing:
``nblocks = len // 33``.

Blocking is always along the **last axis**, which must be a multiple of 32
at the codec level; :func:`pad_block` / callers that own the logical shape
(QTensor, the compressed collectives, pipeline hops) zero-pad and slice
back.  Zero padding never perturbs a block's scale (it cannot raise the
absmax) and decodes to exact zeros.

Everything here is pure jnp (pallas-traceable, no nested jit) plus numpy
float64 oracles (``*_np``) mirroring the jnp semantics bit-for-bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import takum_np
from repro.core.formats import wire_format

BLOCK = 32  #: OCP MX block size
GROUP = BLOCK + 1  #: payload bytes per block: 1 scale byte + 32 element bytes
E8M0_NAN = 255  #: NaN-scale byte (whole block decodes to NaN)
E8M0_BIAS = 127
E8M0_ZERO_BLOCK = 127  #: all-zero-block scale byte (scale 1.0), see module doc

_U = jnp.uint32
_F32_MIN_NORMAL = 1.1754943508222875e-38  # 2**-126, the DAZ threshold


def _bs(fmt):
    """Resolve to a registered block-scaled format, loudly."""
    wf = wire_format(fmt)
    if not wf.is_block_scaled:
        raise ValueError(f"{wf.name!r} is not a block-scaled wire format")
    return wf


def padded_len(n: int) -> int:
    """Smallest multiple of BLOCK >= n."""
    return -(-n // BLOCK) * BLOCK


def payload_len(n: int) -> int:
    """Payload bytes for n elements (n padded to a block multiple)."""
    return (padded_len(n) // BLOCK) * GROUP


def elems_len(payload_cols: int) -> int:
    """Element count carried by a payload of ``payload_cols`` bytes."""
    if payload_cols % GROUP:
        raise ValueError(
            f"block payload length {payload_cols} is not a multiple of {GROUP}"
        )
    return (payload_cols // GROUP) * BLOCK


def pad_block(x, n: int | None = None):
    """Zero-pad the last axis up to a BLOCK multiple (no-op when aligned)."""
    n = x.shape[-1] if n is None else n
    pad = padded_len(n) - n
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _pow2_f32(k):
    """Exact f32 2**k for integer k in [-126, 127] (bit assembly)."""
    kk = jnp.clip(k, -126, 127)
    return jax.lax.bitcast_convert_type(((kk + 127).astype(_U)) << 23, jnp.float32)


def e8m0_decode(scale_bytes):
    """E8M0 byte -> f32 scale: 2**(b - 127); 255 -> NaN; 0 clamps to 2**-126.

    Byte 0 nominally encodes 2**-127, an f32 subnormal this DAZ/FTZ stack
    cannot carry; the encoder never emits it (see :func:`scale_bytes`).
    """
    b = scale_bytes.astype(jnp.int32)
    s = _pow2_f32(jnp.clip(b - E8M0_BIAS, -126, 127))
    return jnp.where(b == E8M0_NAN, jnp.float32(jnp.nan), s)


def scale_bytes(amax, elem_emax: int):
    """Per-block absmax (f32, >= 0 or NaN) -> E8M0 scale byte (uint8).

    ``floor(log2(amax))`` is the f32 biased exponent minus 127 — exact for
    normals; zero/subnormal absmax (DAZ) takes the all-zero-block rule and
    Inf/NaN absmax the NaN-scale rule (module docstring).
    """
    bits = jax.lax.bitcast_convert_type(amax.astype(jnp.float32), _U)
    e = ((bits >> 23) & _U(0xFF)).astype(jnp.int32)
    byte = jnp.clip(e - elem_emax, 1, 254)
    byte = jnp.where(e == 0, E8M0_ZERO_BLOCK, byte)  # zero / DAZ block
    byte = jnp.where(e == 255, E8M0_NAN, byte)  # Inf/NaN in block
    return byte.astype(jnp.uint8)


def elem_cap(fmt) -> float:
    """The element format's largest value below ``2**(emax + 1)`` — the
    saturation rail of the MX element conversion (module docstring)."""
    wf = _bs(fmt)
    top = 2.0 ** (wf.elem_emax + 1)
    vals = wf.elem.decode_np(
        np.arange(1 << (wf.elem.nbits - 1), dtype=np.uint64).astype(wf.elem.np_storage)
    )
    finite = vals[np.isfinite(vals) & (vals < top)]
    return float(np.max(finite))


def block_scale_bytes(x, fmt):
    """f32 [..., n] (n % 32 == 0) -> per-block E8M0 scale bytes [..., n/32]."""
    wf = _bs(fmt)
    n = x.shape[-1]
    if n % BLOCK:
        raise ValueError(f"block-scaled last axis must be a multiple of {BLOCK}, got {n}")
    xb = x.astype(jnp.float32).reshape(x.shape[:-1] + (n // BLOCK, BLOCK))
    amax = jnp.max(jnp.abs(xb), axis=-1)  # NaN/Inf propagate -> NaN-scale block
    return scale_bytes(amax, wf.elem_emax)


def expand_scales(scales):
    """Per-block scale bytes [..., nb] -> one byte per element [..., nb*32]."""
    return jnp.repeat(scales, BLOCK, axis=-1)


def quantize_elems(x, sb, fmt, *, elem_encode=None):
    """The element half of the MX encode, element-wise: f32 ``x`` and its
    block's scale byte ``sb`` (broadcastable) -> element bits (uint32).

    Divides by the scale as an exact power-of-two multiply, applies the
    scaled-binade cap *before* the element encode — so any exact RNE encoder
    of the element format is valid here (clipped values never overflow),
    which is what makes the OFP8 field packers and the takum encode LUTs
    interchangeable in the kernels — and zeroes NaN-scale blocks.
    """
    wf = _bs(fmt)
    sbi = sb.astype(jnp.int32)
    # 127 - byte in [-127, 126] needs the two-step split (single _pow2_f32
    # clips at -126)
    k = E8M0_BIAS - sbi
    ka = jnp.clip(k, -126, 127)
    xs = x.astype(jnp.float32) * _pow2_f32(ka) * _pow2_f32(k - ka)
    cap = jnp.float32(elem_cap(wf))
    xs = jnp.clip(xs, -cap, cap)  # the saturating MX conversion (module doc)
    enc = elem_encode if elem_encode is not None else wf.elem.encode_jnp
    bits = enc(xs).astype(_U)
    # NaN-scale blocks carry zero element bits: decode is NaN regardless
    # (OCP block-NaN), and zeroing keeps the payload deterministic
    return jnp.where(sbi == E8M0_NAN, _U(0), bits)


def dequantize_elems(bits, sb, fmt, *, elem_decode=None):
    """The MX decode, element-wise: element bits and their block's scale
    byte (broadcastable) -> f32 ``scale * element``."""
    wf = _bs(fmt)
    dec = elem_decode if elem_decode is not None else wf.elem.decode_jnp
    return (dec(bits) * e8m0_decode(sb)).astype(jnp.float32)


def block_quantize(x, fmt, *, elem_encode=None):
    """f32 [..., n] (n % 32 == 0) -> (scales [..., n/32] uint8, bits [..., n]).

    ``elem_encode`` overrides the element codec (the kernels pass their
    impl-specific LUT/bits encoder); see :func:`quantize_elems`.
    """
    wf = _bs(fmt)
    n = x.shape[-1]
    sb = block_scale_bytes(x, wf)
    xb = x.astype(jnp.float32).reshape(x.shape[:-1] + (n // BLOCK, BLOCK))
    bits = quantize_elems(xb, sb[..., None], wf, elem_encode=elem_encode)
    return sb, bits.reshape(x.shape).astype(wf.elem.storage)


def block_dequantize(scales, bits, fmt, *, elem_decode=None):
    """(scales [..., n/32], bits [..., n]) -> f32 [..., n].

    ``value = scale * element`` in f32 (OCP decode semantics: overflow past
    f32 goes to Inf, underflow flushes); NaN-scale blocks are all-NaN.
    """
    n = bits.shape[-1]
    bb = bits.reshape(bits.shape[:-1] + (n // BLOCK, BLOCK))
    vals = dequantize_elems(bb, scales[..., None], fmt, elem_decode=elem_decode)
    return vals.reshape(bits.shape[:-1] + (n,))


def pack_payload(scales, bits):
    """(scales [..., nb], bits [..., nb*32]) -> payload uint8 [..., nb*33].

    Interleaved layout: each 33-byte group is [scale_byte, e0..e31] — the
    scale rides next to its element bytes so one contiguous tile fetch
    carries both (the kernel-prologue property the module doc describes).
    """
    nb = scales.shape[-1]
    grp = jnp.concatenate(
        [
            scales[..., None].astype(jnp.uint8),
            bits.reshape(bits.shape[:-1] + (nb, BLOCK)).astype(jnp.uint8),
        ],
        axis=-1,
    )
    return grp.reshape(scales.shape[:-1] + (nb * GROUP,))


def unpack_payload(payload):
    """payload uint8 [..., nb*33] -> (scales [..., nb], bits [..., nb*32])."""
    nb = elems_len(payload.shape[-1]) // BLOCK
    grp = payload.reshape(payload.shape[:-1] + (nb, GROUP))
    return grp[..., 0], grp[..., 1:].reshape(payload.shape[:-1] + (nb * BLOCK,))


def split_payload(payload):
    """payload [..., nb*33] -> (element bits [..., nb*32], per-element scale
    bytes [..., nb*32]): the form the Pallas kernels take a block-scaled
    operand in.  Mosaic cannot split a tile's lanes into 33-byte groups, so
    the container is taken apart by XLA around the kernel call."""
    scales, bits = unpack_payload(payload)
    return bits, expand_scales(scales)


def encode_payload(x, fmt, *, elem_encode=None):
    """f32 [..., n] (n % 32 == 0) -> interleaved wire payload [..., n/32*33]."""
    return pack_payload(*block_quantize(x, fmt, elem_encode=elem_encode))


def decode_payload(payload, fmt, *, elem_decode=None):
    """Interleaved wire payload [..., L] -> f32 [..., L/33*32]."""
    scales, bits = unpack_payload(payload)
    return block_dequantize(scales, bits, fmt, elem_decode=elem_decode)


# ---------------------------------------------------------------------------
# float64 numpy oracles (mirror the jnp semantics bit-for-bit)
# ---------------------------------------------------------------------------


def _daz_np(x):
    """f32-DAZ on f64 values: |x| < 2**-126 flushes to zero, sign preserved
    (the jnp path's f32 underflow keeps the sign bit, and the OFP8 element
    encode emits the -0 pattern for it — the oracle must match bitwise)."""
    x = np.asarray(x, np.float64)
    with np.errstate(invalid="ignore"):
        return np.where(np.abs(x) < _F32_MIN_NORMAL, np.copysign(0.0, x), x)


def _elem_encode_np(wf, xs):
    """f64 element encode with the scaled-binade cap applied (oracle)."""
    cap = elem_cap(wf)
    xs = np.clip(xs, -cap, cap)
    if wf.elem.family == "takum":
        return takum_np.encode(_daz_np(xs), wf.elem.nbits, "linear")
    return wf.elem.encode_np(xs)


def encode_payload_np(x, fmt):
    """f64 [..., n] (n % 32 == 0) -> payload uint8, the jnp path's oracle.

    Mirrors the f32 pipeline exactly: DAZ the inputs, absmax per block,
    byte via the biased f32 exponent, scaled elements rounded through f32
    (the jnp path's one rounding before the element encode), DAZ again.
    """
    wf = _bs(fmt)
    x = _daz_np(x)
    n = x.shape[-1]
    if n % BLOCK:
        raise ValueError(f"block-scaled last axis must be a multiple of {BLOCK}, got {n}")
    xb = x.reshape(x.shape[:-1] + (n // BLOCK, BLOCK))
    amax = np.max(np.abs(xb), axis=-1)
    with np.errstate(invalid="ignore", over="ignore"):
        eb = np.asarray(amax, np.float64).astype(np.float32).view(np.uint32)
    e = ((eb >> 23) & 0xFF).astype(np.int64)
    byte = np.clip(e - wf.elem_emax, 1, 254)
    byte = np.where(e == 0, E8M0_ZERO_BLOCK, byte)
    byte = np.where(e == 255, E8M0_NAN, byte).astype(np.uint8)
    # exact pow2 divide in f64, then the jnp path's f32 rounding + DAZ
    k = E8M0_BIAS - byte.astype(np.int64)
    with np.errstate(over="ignore", invalid="ignore"):
        xs = xb * np.exp2(k.astype(np.float64))[..., None]
        xs = _daz_np(xs.astype(np.float32).astype(np.float64))
    bits = _elem_encode_np(wf, xs).astype(np.uint64)
    bits = np.where(byte[..., None] == E8M0_NAN, 0, bits)
    scales = byte
    grp = np.concatenate(
        [scales[..., None].astype(np.uint8), bits.astype(np.uint8)], axis=-1
    )
    return grp.reshape(x.shape[:-1] + ((n // BLOCK) * GROUP,))


def decode_payload_np(payload, fmt):
    """Payload -> f64 values: exact scale multiply over the element format's
    *kernel-semantics* decode (the f32 decode table — takum elements flush
    c < -126 and saturate c > 127 exactly like the jnp/kernel decoders, so
    the oracle mirrors the wire bit-for-bit; the f32 rounding of the final
    product is the jnp path's and is applied by comparers, not here)."""
    from repro.core.tables import decode_table_f32

    wf = _bs(fmt)
    payload = np.asarray(payload, np.uint8)
    nb = elems_len(payload.shape[-1]) // BLOCK
    grp = payload.reshape(payload.shape[:-1] + (nb, GROUP))
    sb = grp[..., 0].astype(np.int64)
    bits = grp[..., 1:]
    with np.errstate(invalid="ignore"):
        vals = decode_table_f32(wf.elem_name)[bits].astype(np.float64)
    scale = np.exp2(np.clip(sb - E8M0_BIAS, -126, 127).astype(np.float64))
    scale = np.where(sb == E8M0_NAN, np.nan, scale)
    with np.errstate(invalid="ignore"):
        out = vals * scale[..., None]
    return out.reshape(payload.shape[:-1] + (nb * BLOCK,))
