"""Property-based format-conformance suite: every ``WIRE_FORMATS`` entry.

One parametrized harness run against **every** registered wire format —
the parametrization is ``sorted(WIRE_FORMATS)`` itself, so a newly
registered format (e.g. the block-scaled mx* containers this PR adds) is
covered automatically, with no test edits.  Properties:

* **decode-encode idempotence** — ``encode(decode(encode(x))) == encode(x)``
  bitwise, including NaN/Inf inputs.  For the block-scaled formats this is
  exactly why the element conversion saturates to the scaled binade
  (quant.blockscale module doc): without the cap the E8M0 scale is not a
  fixed point of re-encoding.
* **encode monotonicity on finite positives** — decoded round-trips of a
  sorted positive vector stay sorted (one 32-block for the mx formats:
  cross-block comparisons see different scales by design).
* **sign symmetry** — ``roundtrip(-x) == -roundtrip(x)`` valuewise.
* **special-value round-trip** — NaR/NaN/Inf semantics per family: takum
  collapses NaN/Inf to NaR (decodes NaN), E4M3 has no Inf, E5M2/bf16/f32
  keep signed Inf, and a block-scaled container NaNs the *whole block*
  (the OCP NaN-scale rule).
* **jnp codec == f64 oracle** — encode bits identical and decoded values
  identical (after f32 rounding) between the kernel-semantics jnp codec
  and the float64 numpy oracle, on the DAZ domain (f32 subnormal inputs
  flush to zero by design — DESIGN.md §3 — so the property is stated on
  inputs with |x| >= 2**-126 or x == 0).

Hypothesis settings are pinned for CI determinism: fixed example budget,
``deadline=None`` (interpret-mode jax calls are slow and bursty) and
``derandomize=True`` (no random seed — the shrink database never flakes a
tier-1 run).  Without hypothesis installed, tests/_hyp substitutes the
deterministic fixed-seed sampler.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from tests._hyp import HAVE_HYPOTHESIS, given, settings, st

from repro.core.formats import WIRE_FORMATS, wire_format

ALL_FMTS = tuple(sorted(WIRE_FORMATS))

_PINNED = dict(max_examples=25, deadline=None)
if HAVE_HYPOTHESIS:
    _PINNED["derandomize"] = True  # pinned seed: deterministic in tier-1

_F32_MIN_NORMAL = np.float32(1.1754943508222875e-38)  # 2**-126
_F32_MAX = np.float32(3.4028235e38)

#: background values every property array carries besides the sampled pair —
#: spanning magnitudes, signs, exact powers of two, and a rounding tie
_FILLER = [
    0.0, 1.0, -1.0, 0.5, -0.25, 2.0, -8.0, 3.1415927, -0.7071068,
    1e-3, -1e3, 6.5536e4, -2.0**-20, 2.0**20, 1.9375, -1.9375,
    448.0, -448.0, 57344.0, -57344.0, 1e30, -1e-30, 0.1, -0.3,
    7.0, -13.0, 2.0**-126, -2.0**-126, 255.0, -2.5, 1.5, -1.0625,
]
assert len(_FILLER) == 32  # one exact mx block


def _arr(a: float, b: float) -> jnp.ndarray:
    """A 32-long f32 array (one mx block) carrying the sampled pair."""
    vals = [a, b] + _FILLER[2:]
    return jnp.asarray(np.asarray(vals, dtype=np.float32))


def _finite_cap(wf) -> float:
    """Largest input magnitude the format keeps finite: the flat OFP8
    formats overflow into NaN/Inf past their max finite (that behaviour is
    the special-value property, not a monotonicity break); everything else
    — takum saturation, the MX absmax-derived scale — stays finite over
    the whole f32 range."""
    return {"e4m3": 448.0, "e5m2": 57344.0}.get(wf.name, float(_F32_MAX))


def _value_eq(a: np.ndarray, b: np.ndarray) -> bool:
    """Valuewise equality with NaN == NaN and 0.0 == -0.0."""
    a, b = np.asarray(a), np.asarray(b)
    return bool(((a == b) | (np.isnan(a) & np.isnan(b))).all())


# ------------------------------------------------------------- idempotence


@pytest.mark.parametrize("fmt", ALL_FMTS)
@settings(**_PINNED)
@given(
    a=st.floats(width=32, allow_nan=True, allow_infinity=True),
    b=st.floats(width=32, allow_nan=True, allow_infinity=True),
)
def test_decode_encode_idempotent(fmt, a, b):
    """decode . encode is a projection: a second round-trip changes nothing.

    Two layers, both always asserted:

    * **value idempotence** — ``decode(encode(decode(encode(x)))) ==
      decode(encode(x))`` everywhere, specials included.  This holds even
      at the f32 clamp rails (takum decode flushes c < -126 and saturates
      c > 127; re-encoding a flushed 0 gives 0, re-encoding the saturation
      value lands on a code that decodes back to it).
    * **bit idempotence** — ``encode(decode(encode(x))) == encode(x)`` on
      the *interior* lanes, i.e. wherever the decoded value was not
      collapsed by the f32 flush/saturation (there, distinct tail codes
      legitimately re-encode to the collapsed value's code).  For the
      block-scaled formats the payload groups 33 bytes per 32 lanes, so
      the bitwise check runs when the whole array is interior (the scale
      bytes cannot be sliced lanewise) — which the value check backstops.
    """
    wf = wire_format(fmt)
    x = _arr(a, b)
    e1 = wf.encode_jnp(x)
    d1 = wf.decode_jnp(e1)
    e2 = wf.encode_jnp(d1)
    d2 = wf.decode_jnp(e2)
    assert _value_eq(np.asarray(d2), np.asarray(d1)), fmt

    d1n = np.asarray(d1)
    interior = np.isnan(d1n) | np.isinf(d1n) | (
        (d1n != 0) & (np.abs(d1n) < float(_F32_MAX))
    ) | (np.asarray(x) == 0)
    e1n, e2n = np.asarray(e1), np.asarray(e2)
    if wf.is_block_scaled:
        if interior.all():
            np.testing.assert_array_equal(e1n, e2n)
    else:
        np.testing.assert_array_equal(e1n[interior], e2n[interior])


# ------------------------------------------------------------ monotonicity


@pytest.mark.parametrize("fmt", ALL_FMTS)
@settings(**_PINNED)
@given(
    # bounds must be float32 values at width=32 (hypothesis refuses 3.0e38)
    a=st.floats(min_value=0.0, max_value=float(np.float32(3.0e38)), width=32),
    b=st.floats(min_value=0.0, max_value=float(np.float32(3.0e38)), width=32),
)
def test_encode_monotonic_on_finite_positives(fmt, a, b):
    """x <= y (finite positives) => roundtrip(x) <= roundtrip(y).

    Values are clamped into the format's finite range first (E4M3 overflows
    into NaN by design, which is its own property, not a monotonicity
    break).  For block-scaled formats the 32 values share one block, i.e.
    one scale — cross-block order is not a format guarantee.
    """
    wf = wire_format(fmt)
    vals = np.minimum(np.abs(np.asarray(_arr(a, b))), _finite_cap(wf))
    x = jnp.asarray(np.sort(vals))
    y = np.asarray(wf.decode_jnp(wf.encode_jnp(x)))
    assert not np.isnan(y).any() and np.isfinite(y).all(), (fmt, y)
    assert (np.diff(y) >= 0).all(), (fmt, y)


# ----------------------------------------------------------- sign symmetry


@pytest.mark.parametrize("fmt", ALL_FMTS)
@settings(**_PINNED)
@given(
    a=st.floats(width=32, allow_nan=False, allow_infinity=False),
    b=st.floats(width=32, allow_nan=False, allow_infinity=False),
)
def test_sign_symmetry(fmt, a, b):
    """roundtrip(-x) == -roundtrip(x) valuewise (all families encode sign
    losslessly: two's complement for takum, a sign bit elsewhere; the mx
    scale is derived from |x| so negation never moves a block's scale)."""
    wf = wire_format(fmt)
    x = _arr(a, b)
    yp = np.asarray(wf.decode_jnp(wf.encode_jnp(x)))
    ym = np.asarray(wf.decode_jnp(wf.encode_jnp(-x)))
    assert _value_eq(ym, -yp), fmt


# ---------------------------------------------------------- special values


@pytest.mark.parametrize("fmt", ALL_FMTS)
def test_special_value_roundtrip(fmt):
    """NaR/NaN/Inf per family, exercised *inside* a block of finite values."""
    wf = wire_format(fmt)
    base = np.asarray(_FILLER, dtype=np.float32)
    # keep the finite background inside the format's finite range: the flat
    # OFP8 formats would otherwise overflow *other* lanes into NaN/Inf and
    # mask the per-lane claim below
    base = np.clip(base, -_finite_cap(wf), _finite_cap(wf))

    def rt(special):
        v = base.copy()
        v[5] = special
        return np.asarray(wf.decode_jnp(wf.encode_jnp(jnp.asarray(v)))), v

    y, v = rt(np.nan)
    if wf.is_block_scaled:
        # NaN-scale rule: the whole block decodes NaN (OCP MX)
        assert np.isnan(y).all(), fmt
    else:
        assert np.isnan(y[5]) and not np.isnan(np.delete(y, 5)).any(), fmt

    for inf in (np.inf, -np.inf):
        y, v = rt(inf)
        if wf.is_block_scaled:
            assert np.isnan(y).all(), fmt  # Inf also NaNs the block's scale
        elif wf.special == "inf":
            assert y[5] == inf, (fmt, y[5])
        else:  # takum NaR / E4M3 NaN: no infinity exists
            assert np.isnan(y[5]), (fmt, y[5])
            assert not np.isnan(np.delete(y, 5)).any(), fmt


# --------------------------------------------------- jnp == float64 oracle


def _daz(v: np.ndarray) -> np.ndarray:
    """The codecs' documented DAZ domain: f32 subnormals flush to zero."""
    return np.where(np.abs(v) < _F32_MIN_NORMAL, 0.0, v).astype(np.float32)


@pytest.mark.parametrize("fmt", ALL_FMTS)
@settings(**_PINNED)
@given(
    a=st.floats(width=32, allow_nan=False, allow_infinity=False,
                allow_subnormal=False),
    b=st.floats(width=32, allow_nan=False, allow_infinity=False,
                allow_subnormal=False),
)
def test_jnp_codec_agrees_with_f64_oracle(fmt, a, b):
    """encode bits identical, decoded values identical after f32 rounding."""
    wf = wire_format(fmt)
    v = _daz(np.asarray(_arr(a, b)))
    x = jnp.asarray(v)
    bits_j = np.asarray(wf.encode_jnp(x)).astype(np.uint64)
    bits_n = np.asarray(wf.encode_np(v.astype(np.float64))).astype(np.uint64)
    np.testing.assert_array_equal(bits_j, bits_n)
    with np.errstate(invalid="ignore", over="ignore"):
        dec_j = np.asarray(wf.decode_jnp(wf.encode_jnp(x)))
        dec_n = wf.decode_np(
            np.asarray(wf.encode_np(v.astype(np.float64))).astype(wf.np_storage)
        ).astype(np.float32)
        if wf.family == "takum":
            # the jnp decode carries the kernel's f32 clamp (c < -126
            # flushes, c > 127 saturates); the takum_np oracle is exact —
            # map it through the same clamp before comparing
            dec_n = np.where(np.abs(dec_n) < _F32_MIN_NORMAL, 0.0, dec_n)
            dec_n = np.clip(dec_n, -_F32_MAX, _F32_MAX).astype(np.float32)
    assert _value_eq(dec_j, dec_n), fmt


# ------------------------------------------- special-census == f64 oracle


def _random_payload(wf, rng, n):
    """A uniformly random wire payload: every bit pattern is fair game —
    including NaR/NaN/Inf codes and (for mx) the 255 NaN-scale byte."""
    if wf.is_block_scaled:
        nb = -(-n // 32)
        return rng.integers(0, 256, size=nb * 33, dtype=np.uint8)
    info = np.iinfo(wf.np_storage)
    return rng.integers(0, int(info.max) + 1, size=n, dtype=wf.np_storage)


def _oracle_special_count(wf, payload: np.ndarray) -> int:
    """Brute force: decode through the float64 numpy oracle and count the
    lanes that are not finite.  This is the semantics ``count_specials``'
    bit predicates must reproduce without decoding."""
    with np.errstate(invalid="ignore", over="ignore"):
        if wf.is_block_scaled:
            vals = wf.decode_np(payload.astype(np.uint8))
        elif wf.family == "takum":
            vals = wf.decode_np(payload.astype(np.uint64))  # shifted fields
        else:
            vals = wf.decode_np(payload.astype(wf.np_storage))
        return int((~np.isfinite(np.asarray(vals, np.float64))).sum())


@pytest.mark.parametrize("fmt", [f for f in ALL_FMTS if f != "f32"] + ["f32"])
def test_count_specials_matches_f64_oracle_scan(fmt):
    """``count_specials(payload, fmt) == #{~isfinite(decode_np(payload))}``
    on random payloads, for every registered format.

    The health guards (quant/KV/collective surfaces) threshold on this
    census *without* decoding — a predicate/oracle disagreement would make
    the degradation ladder blind to exactly the codes it exists to catch.
    Random payloads cover the space; the crafted tails pin the codes that
    matter (NaR, all NaN/Inf encodings, the mx NaN-scale byte) even when
    the random draw misses them.
    """
    from repro.core.formats import count_specials

    wf = wire_format(fmt)
    rng = np.random.default_rng(hash(fmt) % 2**32)
    for n in (32, 64, 256, 1024):
        payload = _random_payload(wf, rng, n)
        got = int(count_specials(jnp.asarray(payload), fmt))
        want = _oracle_special_count(wf, payload)
        assert got == want, (fmt, n, got, want)

    # crafted: encode a vector that *contains* every special the family has
    specials = np.asarray(
        [np.nan, np.inf, -np.inf, 1.0, -2.5, 0.0] + [3.0] * 26, np.float64
    )
    if wf.is_block_scaled:
        crafted = np.asarray(wf.encode_np(specials))
        # plus a forced NaN-scale block: all 32 lanes special
        forced = crafted.copy()
        forced[0] = 255
        for p, floor in ((crafted, 3), (forced, 32)):
            got = int(count_specials(jnp.asarray(p), fmt))
            assert got == _oracle_special_count(wf, p) and got >= floor, fmt
    else:
        crafted = np.asarray(wf.encode_np(specials)).astype(wf.np_storage)
        got = int(count_specials(jnp.asarray(crafted), fmt))
        want = _oracle_special_count(wf, crafted)
        # takum/e4m3 collapse all three to NaR/NaN (>=1 code); e5m2/bf16/f32
        # keep signed infinities distinct (3 codes)
        assert got == want and want >= (3 if wf.special == "inf" else 1), fmt


# ----------------------------------------------------------- registry edge


def test_conformance_covers_whole_registry():
    """The suite's parametrization *is* the registry: a format registered in
    core.formats but missing here is impossible by construction."""
    assert set(ALL_FMTS) == set(WIRE_FORMATS)
    assert {"mxe4m3", "mxe5m2", "mxt8"} <= set(ALL_FMTS)


def test_unknown_format_rejected():
    with pytest.raises(KeyError):
        wire_format("mxfp4")
