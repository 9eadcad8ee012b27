"""Shared helpers for the Pallas TPU kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are
validated on CPU in interpret mode; ``interpret_default()`` picks the mode
from the runtime backend so the same call sites lower natively on TPU.

The in-kernel takum decode is the branch-free bit-assembly variant
(:func:`repro.core.takum.takum_decode_f32bits` inlined here in kernel-safe
form): pure integer ops + one bitcast, no transcendentals — this mirrors the
paper's "common ≤12-bit decoder for all precisions" in MXU-feedable form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_U = jnp.uint32
_I = jnp.int32


def interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def round_up(x: int, m: int) -> int:
    """Smallest multiple of m that is >= x."""
    return -(-x // m) * m


def choose_block(dim: int, want: int, align: int) -> int:
    """Block size for a grid dim that may not divide ``dim``.

    Returns ``want`` clamped to the aligned cover of ``dim``: small dims get
    one (padded) tile, large dims keep the requested MXU-aligned block.  The
    grid is then ``pl.cdiv(dim, block)`` with a masked edge tile — arbitrary
    M/N/K and sequence lengths keep 8/128-multiple tiles instead of the old
    degrade-to-divisor fallback (which collapsed prime dims to block size 1).
    """
    return min(want, round_up(dim, align))


def sublane_align(dtype) -> int:
    """Row alignment of a tile of ``dtype`` on TPU: 8 sublanes of 32-bit
    words, so 16 rows for 16-bit and 32 rows for 8-bit packed operands."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def dim_mask(tile_shape, axis: int, dim: int, block: int, pid):
    """Edge-tile validity mask: True where global index along ``axis`` < dim.

    ``pid`` is the grid coordinate of this tile along ``axis``'s grid dim.
    Call only when ``dim % block != 0`` (trace-time decision); interior tiles
    then pay a single cheap select.  Padding lanes read garbage (NaN in
    interpret mode, undefined on TPU), so inputs feeding a contraction or a
    softmax must be masked *before* use — packed takum bits are masked to 0,
    which decodes to 0.0.
    """
    ids = jax.lax.broadcasted_iota(jnp.int32, tile_shape, axis)
    return ids < (dim - pid * block)


def decode_takum_f32(bits, n: int):
    """Kernel-safe linear-takum decode: uint bits -> float32 values.

    Identical semantics to ``takum.takum_decode_f32bits`` (c > 127 saturates
    to f32 max-finite, c < -126 flushes to zero, NaR -> NaN); n in {8, 16}.
    """
    b = bits.astype(_U) & _U((1 << n) - 1)
    is_zero = b == 0
    is_nar = b == _U(1 << (n - 1))
    neg = (b >> (n - 1)) & 1
    mag = jnp.where(neg == 1, (_U(0) - b) & _U((1 << n) - 1), b)

    D = (mag >> (n - 2)) & 1
    R = ((mag >> (n - 5)) & 7).astype(_I)
    r = jnp.where(D == 1, R, 7 - R)
    rem = n - 5
    rem_v = mag & _U((1 << rem) - 1)

    have = rem >= r
    C_full = rem_v >> jnp.maximum(_I(rem) - r, 0).astype(_U)
    C_pad = rem_v << jnp.clip(r - rem, 0, 31).astype(_U)
    C = jnp.where(have, C_full, C_pad)
    p = jnp.maximum(rem - r, 0)
    M = jnp.where(have, rem_v & ((_U(1) << jnp.minimum(p, 31).astype(_U)) - 1), _U(0))
    c = jnp.where(
        D == 1,
        ((_I(1) << jnp.minimum(r, 30)) - 1) + C.astype(_I),
        1 - (_I(1) << jnp.minimum(r + 1, 30)) + C.astype(_I),
    )

    sat_hi = c > 127
    flush = c < -126
    e_fld = (jnp.clip(c, -126, 127) + 127).astype(_U)
    m_fld = M << jnp.clip(23 - p, 0, 23).astype(_U)
    out = (e_fld << 23) | m_fld
    out = jnp.where(sat_hi, _U(0x7F7FFFFF), out)
    out = jnp.where(flush | is_zero, _U(0), out)
    out = jnp.where(is_nar, _U(0x7FC00000), out)
    out = jnp.where(is_zero | is_nar, out, out | (neg << 31))
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def encode_takum_from_f32(x, n: int):
    """Kernel-safe linear-takum encode: float32 -> uint32 low-n-bit patterns.

    Same bit-exact semantics as ``takum.takum_encode`` (linear mode): RNE on
    the left-aligned body, saturation, two's-complement negatives, NaR for
    NaN/Inf.  All ops are uint32 shifts/compares + population_count.
    """
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, _U)
    neg_in = (bits >> 31) & 1
    absbits = bits & _U(0x7FFFFFFF)
    # DAZ: f32 subnormals (raw exponent 0) encode to 0, matching XLA CPU/TPU
    # float semantics and the jnp reference codec (DESIGN.md §3)
    is_zero = absbits < _U(0x00800000)
    is_nar = absbits >= _U(0x7F800000)  # inf/nan

    raw_e = (absbits >> 23).astype(_I)
    raw_m = absbits & _U(0x7FFFFF)
    e = raw_e - 127
    m23 = raw_m

    # header from characteristic c == e (f32 range never saturates takum)
    cneg = e < 0
    g = jnp.where(cneg, -e, e + 1).astype(_U)
    gv = g | (g >> 1); gv = gv | (gv >> 2); gv = gv | (gv >> 4)
    r = (jax.lax.population_count(gv).astype(_I) - 1)
    ru = r.astype(_U)
    C = jnp.where(cneg, e + (_I(1) << (r + 1)) - 1, e - ((_I(1) << r) - 1)).astype(_U)
    R = jnp.where(cneg, 7 - r, r).astype(_U)
    D = jnp.where(cneg, _U(0), _U(1))
    H = (D << (ru + 3)) | (R << ru) | C  # 4 + r bits

    # body = H:m23 left-aligned; round to keep n-1 bits (t = 28 + r - n <= 27)
    hi = H >> 9
    lo = ((H & _U(0x1FF)) << 23) | m23
    # shift amounts are computed in int32 and cast at the shift: Mosaic has
    # no unsigned min/max (arith.minui/maxui do not legalize on TPU)
    t = (28 + r - n).astype(_I)
    tc = jnp.maximum(t, 1)
    up_sh = jnp.minimum(32 - tc, 31).astype(_U)
    kept = jnp.where(t == 0, lo, (lo >> jnp.minimum(tc, 31).astype(_U)) | (hi << up_sh))
    g1 = tc - 1
    guard = jnp.where(
        g1 >= 32,
        (hi >> jnp.clip(g1 - 32, 0, 31).astype(_U)) & 1,
        (lo >> jnp.minimum(g1, 31).astype(_U)) & 1,
    )
    guard = jnp.where(t >= 1, guard, _U(0))
    below = jnp.where(g1 == 0, _U(0), (_U(1) << jnp.minimum(g1, 31).astype(_U)) - 1)
    sticky = (lo & below) != 0
    round_up = (guard == 1) & (sticky | ((kept & 1) == 1))
    mag = kept + round_up.astype(_U)
    # t < 0 impossible for n <= 28 with f32 input (t = 28 + r - n, r >= 0)
    mag = jnp.clip(mag.astype(_I), 1, (1 << (n - 1)) - 1).astype(_U)

    enc = jnp.where(neg_in == 1, (_U(0) - mag) & _U((1 << n) - 1), mag)
    enc = jnp.where(is_zero, _U(0), enc)
    enc = jnp.where(is_nar, _U(1 << (n - 1)), enc)
    return enc
