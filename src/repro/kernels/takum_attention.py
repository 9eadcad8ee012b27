"""Pallas TPU kernel: flash-decode attention over a wire-format KV cache.

The memory-wall case the paper closes with ("particular emphasis on 8- and
16-bit types"): single-token decode attention is HBM-bandwidth-bound on the
KV cache read, so storing KV as a packed 8/16-bit wire format (takum-8/16,
OFP8 E4M3/E5M2, bf16) cuts the dominant roofline term 2-4x vs f32.  K/V
tiles are decoded in VMEM right before the MXU, either via the family's
branch-free bit decode or the VMEM decode table (``decode_impl``, LUT
default for the 8-bit formats) — the same gather kernel serves every
registered format, which is what makes the takum-vs-OFP8 KV-cache
head-to-head an apples-to-apples measurement.

Layout: q [B, H, d] f32, kv cache [B, Hkv, S, d] packed takum-n (GQA: each kv
head serves g = H/Hkv query heads).  Grid (B, Hkv, cdiv(S, bs)); online
softmax with running (max, denom, acc) in VMEM scratch across the S blocks.
Arbitrary sequence lengths are supported via a padded edge tile: padded
logit columns are masked to -inf (-> zero softmax weight) and padded V rows
are masked to bit pattern 0 (-> decode 0.0) so the weighted sum stays clean.

Arbitrary head dims d and GQA groups g are supported the same way as S:
blocks are padded up to TPU tile alignment (d -> lane multiple, g ->
sublane multiple) and the padding lanes are masked *inside the kernel* —
q's padded g rows / d columns to 0.0, K/V's padded d columns to bit pattern
0 (decode 0.0).  No operand is ever copied: the packed KV cache streams
through unchanged (the whole point of the kernel is that packed-cache read)
and the out-of-range output rows/columns are dropped by the clipped store.
Exactness of the real rows/columns is preserved because the extra terms in
every contraction are exact zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import wire_format
from repro.quant import blockscale
from .common import choose_block, dim_mask, interpret_default, round_up, sublane_align
from .lut import (
    decode_table_operand,
    encode_epilogue,
    encode_epilogue_operands,
    resolve_impl,
    resolve_out_fmt,
    wire_decode_fn,
)

_LANE = 128
_SUBLANE = 8


def _decode_attn_kernel(fmt, impl, S, bs, g, d, scale, out_fmt, out_impl, nenc, *refs):
    ndec = 1 if impl == "lut" else 0
    enc_tabs = refs[ndec : ndec + nenc]
    mx = wire_format(fmt).is_block_scaled
    nkv = 2 if mx else 1  # block-scaled K/V come as (bits, scale bytes)
    opnds = refs[ndec + nenc :]
    q_ref, k_refs, v_refs = opnds[0], opnds[1 : 1 + nkv], opnds[1 + nkv : 1 + 2 * nkv]
    o_ref, m_ref, l_ref, acc_ref = opnds[1 + 2 * nkv :]
    decode = wire_decode_fn(fmt, impl, refs[0] if impl == "lut" else None)
    out_mx = out_fmt is not None and wire_format(out_fmt).is_block_scaled

    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # [gp, dp] f32
    gp, dp = q.shape
    if gp != g:
        # padded q rows -> 0.0 (uniform softmax over finite values; the rows
        # are dropped by the clipped output store)
        q = jnp.where(dim_mask(q.shape, 0, g, gp, 0), q, 0.0)
    if dp != d:
        # padded d lanes: q cols -> 0.0, K/V cols -> 0.0, so every
        # contraction only gains exact-zero terms
        q = jnp.where(dim_mask(q.shape, 1, d, dp, 0), q, 0.0)
    kt = [r[0, 0] for r in k_refs]  # [bs, dp] packed bits (+ scale bytes)
    vt = [r[0, 0] for r in v_refs]
    if mx:
        # a garbage scale byte may decode to NaN: mask after the decode
        k, v = decode(*kt), decode(*vt)
        if dp != d:
            k = jnp.where(dim_mask(k.shape, 1, d, dp, 0), k, 0.0)
            v = jnp.where(dim_mask(v.shape, 1, d, dp, 0), v, 0.0)
        if S % bs:
            v = jnp.where(dim_mask(v.shape, 0, S, bs, s), v, 0.0)
    else:
        # padded bits -> 0 -> decode 0.0
        kb, vb = kt[0], vt[0]
        if dp != d:
            kb = jnp.where(dim_mask(kb.shape, 1, d, dp, 0), kb, 0)
            vb = jnp.where(dim_mask(vb.shape, 1, d, dp, 0), vb, 0)
        if S % bs:
            # padded V rows (their weight is 0 below, but 0 * garbage-NaN
            # would still poison the accumulator)
            vb = jnp.where(dim_mask(vb.shape, 0, S, bs, s), vb, 0)
        k, v = decode(kb), decode(vb)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [g, bs]
    if S % bs:
        # padded K rows produced garbage logit columns: mask to -inf
        logits = jnp.where(dim_mask(logits.shape, 1, S, bs, s), logits, -jnp.inf)

    m_prev = m_ref[:, :1]  # [g, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)  # [g, bs]
    alpha = jnp.exp(m_prev - m_new)  # [g, 1]

    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(s == pl.num_programs(2) - 1)
    def _flush():
        out = acc_ref[...] / l_ref[:, :1]
        if out_fmt is not None:
            # fused epilogue: the attention output leaves VMEM as wire bits
            # (e.g. straight back into a quantised residual/KV consumer);
            # padded g/d lanes encode garbage the clipped store drops.  A
            # block-scaled out_fmt first drops the padded d lanes (their
            # exact zeros would otherwise join real 32-blocks and, worse,
            # widen the payload past the store) and emits [gp, d/32*33].
            if out_mx:
                out = encode_epilogue(out_fmt, out_impl, enc_tabs)(out[:, :d])
            else:
                out = encode_epilogue(out_fmt, out_impl, enc_tabs)(out)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "block_s", "interpret", "decode_impl", "out_fmt",
                     "encode_impl"),
)
def takum_decode_attention(
    q, k_bits, v_bits, fmt, *, block_s=512, interpret=None, decode_impl=None,
    out_fmt=None, encode_impl=None,
):
    """One-token decode attention; returns [B, H, d] f32.

    q: [B, H, d] f32; k_bits/v_bits: [B, Hkv, S, d] packed wire-format bits
    (``fmt``: registered name or bare takum width).  S may be any length
    (padded edge tile); d and g = H/Hkv may be arbitrary (zero-padded to
    lane/sublane alignment outside the kernel).

    ``out_fmt`` fuses the output wire encode into the flush epilogue and
    returns packed [B, H, d] ``out_fmt`` bits instead of f32 — semantics
    ``encode(attention(...))`` (``ref.fused_decode_attention_ref``), with
    ``encode_impl`` selecting the epilogue codec strategy.
    """
    interpret = interpret_default() if interpret is None else interpret
    wf = wire_format(fmt)
    name = wf.name
    impl = resolve_impl(decode_impl, name)
    out_fmt, out_impl = resolve_out_fmt(out_fmt, encode_impl)
    out_mx = out_fmt is not None and wire_format(out_fmt).is_block_scaled
    B, H, d = q.shape
    if wf.is_block_scaled:
        # KV tiles enter split into (element bits, per-element scale bytes):
        # XLA takes the container apart, since Mosaic cannot split lanes
        # into 33-byte groups
        if k_bits.shape[-1] != blockscale.payload_len(d) or d % blockscale.BLOCK:
            raise ValueError(
                f"block-scaled KV cache needs a 32-multiple head dim and a "
                f"{blockscale.payload_len(d)}-byte payload, got d={d}, "
                f"payload {k_bits.shape[-1]}"
            )
        ks = list(blockscale.split_payload(k_bits))
        vs = list(blockscale.split_payload(v_bits))
    else:
        ks, vs = [k_bits], [v_bits]
    _, Hkv, S, dk = ks[0].shape
    assert H % Hkv == 0
    g = H // Hkv
    assert dk == d, (d, dk)
    if out_mx and d % blockscale.BLOCK:
        raise ValueError(
            f"block-scaled out_fmt needs a 32-multiple head dim, got {d}"
        )
    bs = choose_block(S, block_s, sublane_align(ks[0].dtype))
    scale = float(d) ** -0.5  # true head dim: padding adds exact-zero terms

    qg = q.reshape(B, Hkv, g, d)
    dp, gp = round_up(d, _LANE), round_up(g, _SUBLANE)

    grid = (B, Hkv, pl.cdiv(S, bs))
    # blocks are tile-aligned covers of (g, d); edge lanes are masked inside
    # the kernel and the packed KV cache streams through uncopied
    kv_spec = pl.BlockSpec((1, 1, bs, dp), lambda b, h, s: (b, h, s, 0))
    in_specs = [pl.BlockSpec((1, 1, gp, dp), lambda b, h, s: (b, h, 0, 0))]
    in_specs += [kv_spec] * (len(ks) + len(vs))
    args = [qg, *ks, *vs]
    enc_tabs = encode_epilogue_operands(out_fmt, out_impl)
    for t in reversed(enc_tabs):
        in_specs.insert(0, pl.BlockSpec(t.shape, lambda b, h, s: (0, 0)))
        args.insert(0, t)
    if impl == "lut":
        tab = decode_table_operand(name)
        in_specs.insert(0, pl.BlockSpec(tab.shape, lambda b, h, s: (0, 0)))
        args.insert(0, tab)
    out_dtype = jnp.float32 if out_fmt is None else wire_format(out_fmt).storage
    d_out = blockscale.payload_len(d) if out_mx else d
    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, name, impl, S, bs, g, d, scale,
            out_fmt, out_impl, len(enc_tabs),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, gp, d_out if out_mx else dp), lambda b, h, s: (b, h, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, d_out), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((gp, _LANE), jnp.float32),
            pltpu.VMEM((gp, _LANE), jnp.float32),
            pltpu.VMEM((gp, dp), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, d_out)
