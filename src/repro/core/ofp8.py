"""OCP 8-bit floating point (OFP8) E4M3 / E5M2 codecs, JAX + numpy.

These are the AVX10.2 formats the paper proposes to replace (HF8/BF8 in Intel
nomenclature).  E4M3 follows the OCP spec: bias 7, no infinities, S.1111.111
is NaN, max finite 448.  E5M2 is IEEE-754 binary8-like: bias 15, has
infinities and NaNs, max finite 57344.

The JAX paths are hand-rolled bit conversions (they are also the reference
semantics for the ISA layer's VCVT instructions); the numpy paths delegate to
``ml_dtypes`` (authoritative) and are cross-checked against the JAX paths in
tests.  Conversions are round-to-nearest-even, non-saturating by default
(overflow -> NaN/Inf, matching the paper's "dynamic range exceeded"
accounting); ``saturate=True`` gives the AVX10.2 ``...S`` instruction flavour.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

_U = jnp.uint32

SPECS = {
    "e4m3": dict(ebits=4, mbits=3, bias=7, max_finite=448.0, has_inf=False),
    "e5m2": dict(ebits=5, mbits=2, bias=15, max_finite=57344.0, has_inf=True),
}

_ML_DTYPES = {"e4m3": ml_dtypes.float8_e4m3fn, "e5m2": ml_dtypes.float8_e5m2}


def ml_dtype(fmt: str):
    """Public accessor: the ``ml_dtypes`` scalar type backing an OFP8 format."""
    return _ML_DTYPES[fmt]


def encode_jnp(x, fmt: str = "e4m3", saturate: bool = False):
    """float32 -> 8-bit OFP8 patterns (uint8), RNE.

    Unjitted body (kernel-safe: pure jnp ops, traceable inside pallas);
    :func:`encode` is the jitted public wrapper.
    """
    spec = SPECS[fmt]
    eb, mb, bias = spec["ebits"], spec["mbits"], spec["bias"]
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits >> 31
    absbits = bits & _U(0x7FFFFFFF)

    is_nan = jnp.isnan(x)
    is_inf = jnp.isinf(x)

    e = (absbits >> 23).astype(jnp.int32) - 127  # unbiased f32 exponent
    # subnormal target range: e < 1 - bias; shift mantissa accordingly
    e_t = e + bias  # target biased exponent
    # round the 23-bit mantissa (with implicit 1 for subnormal shifts) to mb bits
    m23 = absbits & _U(0x7FFFFF)
    full = m23 | _U(1 << 23)  # implicit one at bit 23

    # normal: keep mb bits of m23;  subnormal: shift `full` right extra
    extra = jnp.clip(1 - e_t, 0, 24)  # how far below the normal range
    t = (23 - mb) + extra  # discard t bits of `full` (sans implicit for normal)
    src = jnp.where(extra > 0, full, m23)
    tc = jnp.clip(t, 1, 31).astype(_U)
    kept = src >> tc
    guard = (src >> (tc - 1)) & 1
    sticky = (src & ((_U(1) << (tc - 1)) - 1)) != 0
    kept = kept + ((guard == 1) & (sticky | ((kept & 1) == 1))).astype(_U)

    # assemble; kept may carry into the exponent (works for both ranges)
    e_sub = jnp.where(extra > 0, 0, e_t)
    mag = (jnp.maximum(e_sub, 0).astype(_U) << mb) + kept

    # flush-to-zero when everything rounds away; f32 subnormal inputs -> 0 too
    mag = jnp.where(absbits == 0, _U(0), mag)
    mag = jnp.where(e < -126, _U(0), mag)  # f32 subnormals: below every OFP8

    max_mag_finite = (((1 << eb) - 1) << mb | ((1 << mb) - 1)) if not spec["has_inf"] else (
        ((1 << eb) - 2) << mb | ((1 << mb) - 1)
    )
    if fmt == "e4m3":
        max_mag_finite = 0x7E  # S.1111.110 = 448; S.1111.111 is NaN
    nan_mag = _U(0x7F) if fmt == "e4m3" else _U(0x7E | 0x01)  # e5m2: 0x7D-0x7F NaN
    inf_mag = _U(0x7C) if spec["has_inf"] else nan_mag

    overflow = mag > max_mag_finite
    mag = jnp.where(
        overflow, jnp.where(saturate, _U(max_mag_finite), inf_mag if spec["has_inf"] else nan_mag), mag
    )
    mag = jnp.where(is_inf, jnp.where(saturate & (not spec["has_inf"]), _U(max_mag_finite), inf_mag), mag)
    mag = jnp.where(is_nan, nan_mag, mag)
    out = (sign << 7) | mag
    return out.astype(jnp.uint8)


encode = jax.jit(encode_jnp, static_argnames=("fmt", "saturate"))


def encode_sr_jnp(x, rnd_bits, fmt: str = "e4m3"):
    """Stochastically-rounded f32 -> OFP8 encode (unjitted, kernel-safe).

    OCP defines no SR conversion for OFP8; this is the documented choice
    (DESIGN.md §6), mirroring ``takum_encode_sr``: *truncate plus uniform
    dither* — add ``U[0, 2**t)`` (from ``rnd_bits``, uint32) below the ``t``
    kept-bit boundary of the magnitude bit string, then truncate (round
    toward zero).  Properties:

    * zero dither reduces to RZ truncation (tested exactly);
    * between two adjacent codes the round-up probability is exactly the
      fractional position, so the encode is statistically unbiased where
      the code grid is locally uniform — including across binade
      boundaries, because the dither carry walks the magnitude code into
      the next exponent (consecutive codes), and into the subnormal range,
      which shares the truncate-and-carry path;
    * dither past the top finite code follows the format's overflow rule
      (E4M3 -> NaN, E5M2 -> Inf), like the RNE encode's
      round-as-if-unbounded-then-replace;
    * the dither field is 31 bits wide; deeper discards (t > 31) pre-shift
      the source by t - 31 so the round-up probability stays src/2**t to
      within the dropped low source bits.  Inputs below the 24-bit
      subnormal alignment window (|x| < ~2**-30 for E4M3) truncate to
      zero, forfeiting their < 2**-21 round-up probability (f32-subnormal
      inputs are DAZ anyway).
    """
    spec = SPECS[fmt]
    eb, mb, bias = spec["ebits"], spec["mbits"], spec["bias"]
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = bits >> 31
    absbits = bits & _U(0x7FFFFFFF)

    is_nan = jnp.isnan(x)
    is_inf = jnp.isinf(x)

    e = (absbits >> 23).astype(jnp.int32) - 127
    e_t = e + bias
    m23 = absbits & _U(0x7FFFFF)
    full = m23 | _U(1 << 23)

    extra = jnp.clip(1 - e_t, 0, 24)
    t = (23 - mb) + extra
    src = jnp.where(extra > 0, full, m23)
    # t can exceed the 31-bit dither field (deep below the subnormals):
    # pre-shift the source so (src' + U[0, 2**31)) >> 31 keeps the round-up
    # probability at src/2**t — clipping the shift alone would inflate it
    # by 2**(t-31), an upward bias of up to ~8e6x on tiny gradients
    over = jnp.clip(t - 31, 0, 31).astype(_U)
    src = src >> over
    tc = jnp.clip(t, 1, 31).astype(_U)
    # truncate + dither: kept = (src + U[0, 2**t)) >> t — the only change
    # vs the RNE tail (src <= 2**24 and dither < 2**31: no uint32 overflow)
    dither = rnd_bits.astype(_U) & ((_U(1) << tc) - _U(1))
    kept = (src + dither) >> tc
    # past the subnormal alignment window the src scale itself is clipped
    # (extra caps at 24): truncate those to zero per the documented choice
    kept = jnp.where(1 - e_t > 24, _U(0), kept)

    e_sub = jnp.where(extra > 0, 0, e_t)
    mag = (jnp.maximum(e_sub, 0).astype(_U) << mb) + kept
    mag = jnp.where(absbits == 0, _U(0), mag)
    mag = jnp.where(e < -126, _U(0), mag)  # DAZ: f32 subnormal inputs

    max_mag_finite = _U(0x7E) if fmt == "e4m3" else _U(0x7B)
    nan_mag = _U(0x7F)
    inf_mag = _U(0x7C) if spec["has_inf"] else nan_mag
    overflow = mag > max_mag_finite
    mag = jnp.where(overflow, inf_mag if spec["has_inf"] else nan_mag, mag)
    mag = jnp.where(is_inf, inf_mag, mag)
    mag = jnp.where(is_nan, nan_mag, mag)
    return ((sign << 7) | mag).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("fmt",))
def encode_sr(x, key, fmt: str = "e4m3"):
    """Stochastically-rounded OFP8 encode (for gradient/optimizer surfaces):
    draws the uniform dither from ``key`` and calls :func:`encode_sr_jnp`."""
    rnd = jax.random.bits(key, shape=jnp.shape(x), dtype=jnp.uint32)
    return encode_sr_jnp(x, rnd, fmt)


def decode_jnp(bits, fmt: str = "e4m3"):
    """8-bit OFP8 patterns -> float32 (unjitted body, kernel-safe)."""
    spec = SPECS[fmt]
    eb, mb, bias = spec["ebits"], spec["mbits"], spec["bias"]
    from .takum import _pow2_f32  # exact 2**k in f32 (bit assembly)

    b = bits.astype(_U)
    sign = (b >> 7) & 1
    e_f = ((b >> mb) & ((1 << eb) - 1)).astype(jnp.int32)
    # via int32: Mosaic has no uint32 -> f32 conversion
    m_f = (b & ((1 << mb) - 1)).astype(jnp.int32).astype(jnp.float32)

    normal = (1.0 + m_f * (2.0**-mb)) * _pow2_f32(e_f - bias)
    subn = m_f * (2.0**-mb) * _pow2_f32(jnp.full_like(e_f, 1 - bias))
    val = jnp.where(e_f == 0, subn, normal)

    if spec["has_inf"]:
        is_inf = (e_f == (1 << eb) - 1) & (m_f == 0)
        is_nan = (e_f == (1 << eb) - 1) & (m_f != 0)
        val = jnp.where(is_inf, jnp.float32(jnp.inf), val)
    else:
        is_nan = (b & _U(0x7F)) == _U(0x7F)
    val = jnp.where(is_nan, jnp.float32(jnp.nan), val)
    return jnp.where(sign == 1, -val, val).astype(jnp.float32)


decode = jax.jit(decode_jnp, static_argnames=("fmt",))


# --- numpy (ml_dtypes) paths -------------------------------------------------


def encode_np(x, fmt: str = "e4m3"):
    """float64 -> OFP8 bit patterns via ml_dtypes (RNE, overflow->NaN/Inf)."""
    with np.errstate(invalid="ignore"):  # NaN/Inf casts are well-defined here
        arr = np.asarray(x, dtype=np.float64).astype(_ML_DTYPES[fmt])
    return arr.view(np.uint8)


def decode_np(bits, fmt: str = "e4m3"):
    with np.errstate(invalid="ignore"):
        return np.asarray(bits, dtype=np.uint8).view(_ML_DTYPES[fmt]).astype(np.float64)
