"""Pallas TPU kernel: dequantising wire-format matmul (the VDPPT* widening dots).

Computes ``x @ decode(w)`` with w stored as packed wire-format bits (takum
8/16, OFP8 E4M3/E5M2, or bf16) in HBM and decoded tile-by-tile in VMEM
before hitting the MXU.  This is the TPU-native adaptation of the paper's
widening dot-product instructions (F08 -> VDPPT8PT16 etc.): the wire format
is the storage/transport format, the MXU replaces the SIMD lane,
accumulation is f32 — and because the decode step is a format handle, the
paper's head-to-head (uniform takum vs the IEEE-derived zoo) runs through
*identical* kernel code.

Grid: (cdiv(M,bm), cdiv(N,bn), cdiv(K,bk)), K innermost; one f32 [bm, bn]
accumulator tile lives in VMEM scratch across the K steps.  Arbitrary
(M, K, N) are supported via padded edge tiles: blocks stay MXU-aligned
(8/128 multiples by default) and the K-dim padding lanes are masked to zero
on *both* operands before the dot (padding reads are garbage — NaN in
interpret mode — and NaN * 0 would poison the accumulator).  M/N padding
needs no masks: out-of-range output rows/cols are dropped by the clipped
store.

The in-VMEM dequant step is selectable via ``decode_impl``: ``"bits"`` is
the branch-free integer decode, ``"lut"`` gathers from the precomputed
VMEM-resident table (default for takum8; see repro.kernels.lut).

``out_fmt`` fuses the *output* wire encode into the flush epilogue: the f32
accumulator tile is encoded to packed wire bits in-register and the store
writes uint8/uint16 — producers that feed a quantised consumer (QTensor
requantise, KV append, grad compression) skip the f32 HBM round-trip a
standalone codec kernel would need.  The epilogue owns no rounding policy of
its own: it applies the format's RNE encode to exactly the f32 values the
unfused kernel would have written (DESIGN.md §6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import wire_format
from repro.quant import blockscale
from .common import choose_block, dim_mask, interpret_default, sublane_align
from .lut import (
    decode_table_operand,
    encode_epilogue,
    encode_epilogue_operands,
    resolve_impl,
    resolve_out_fmt,
    wire_decode_fn,
)


def _mm_kernel(fmt, impl, dual, K, bk, out_fmt, out_impl, nenc, *refs):
    ndec = 1 if impl == "lut" else 0
    mx = wire_format(fmt).is_block_scaled
    nw = 2 if mx else 1  # block-scaled operands come as (bits, scale bytes)
    nx = nw if dual else 1
    enc_tabs = refs[ndec : ndec + nenc]
    opnds = refs[ndec + nenc :]
    x_refs, w_refs = opnds[:nx], opnds[nx : nx + nw]
    o_ref, acc_ref = opnds[nx + nw :]
    decode = wire_decode_fn(fmt, impl, refs[0] if impl == "lut" else None)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kid = pl.program_id(2)

    def packed(tile_refs, axis):
        # VMEM dequant of a packed tile with its K-padding zeroed: bits are
        # masked to 0 (decodes to 0.0); block-scaled tiles are masked after
        # the decode, since a garbage scale byte may decode to NaN
        tiles = [r[...] for r in tile_refs]
        if K % bk and not mx:
            tiles[0] = jnp.where(dim_mask(tiles[0].shape, axis, K, bk, kid), tiles[0], 0)
        v = decode(*tiles)
        if K % bk and mx:
            v = jnp.where(dim_mask(v.shape, axis, K, bk, kid), v, 0.0)
        return v

    w = packed(w_refs, 0)
    if dual:
        x = packed(x_refs, 1)
    else:
        x = x_refs[0][...]
        if K % bk:
            x = jnp.where(dim_mask(x.shape, 1, K, bk, kid), x, 0)
        x = x.astype(jnp.float32)

    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        acc = acc_ref[...]
        if out_fmt is not None:
            # fused epilogue: encode the output tile in-register — the wire
            # bits hit HBM directly, no f32 round-trip for a codec kernel.
            # M/N padding lanes encode garbage that the clipped store drops
            # (element-wise, same as the standalone codec kernel's edges).
            acc = encode_epilogue(out_fmt, out_impl, enc_tabs)(acc)
        o_ref[...] = acc.astype(o_ref.dtype)


_pc = blockscale.payload_len  # element-tile width -> payload-tile width


def _call(fmt, impl, dual, x, w, out_dtype, out_fmt, out_impl, bm, bn, bk, interpret):
    mx = wire_format(fmt).is_block_scaled
    out_mx = out_fmt is not None and wire_format(out_fmt).is_block_scaled
    # block-scaled payloads enter the kernel split into (element bits,
    # per-element scale bytes): XLA takes the container apart, since Mosaic
    # cannot split lanes into 33-byte groups.  w is blocked along N, a dual
    # x along K — after the split both are plain [rows, elems] operands
    ws = list(blockscale.split_payload(w)) if mx else [w]
    xs = list(blockscale.split_payload(x)) if dual and mx else [x]
    M, K = xs[0].shape
    K2, N = ws[0].shape
    assert K == K2, (x.shape, w.shape)
    if out_mx and N % blockscale.BLOCK:
        raise ValueError(
            f"block-scaled out_fmt needs a 32-multiple N, got {N}"
        )
    bm = choose_block(M, bm, sublane_align(xs[0].dtype))
    bn = choose_block(N, bn, 128)
    bk = choose_block(K, bk, 128)
    grid = (pl.cdiv(M, bm), pl.cdiv(N, bn), pl.cdiv(K, bk))
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)) for _ in xs]
    in_specs += [pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)) for _ in ws]
    args = xs + ws
    enc_tabs = encode_epilogue_operands(out_fmt, out_impl)
    for t in reversed(enc_tabs):
        in_specs.insert(0, pl.BlockSpec(t.shape, lambda i, j, k: (0, 0)))
        args.insert(0, t)
    if impl == "lut":
        tab = decode_table_operand(fmt)
        in_specs.insert(0, pl.BlockSpec(tab.shape, lambda i, j, k: (0, 0)))
        args.insert(0, tab)
    if out_fmt is not None:
        out_dtype = wire_format(out_fmt).storage
    out_bn, out_n = (_pc(bn), _pc(N)) if out_mx else (bn, N)
    return pl.pallas_call(
        functools.partial(
            _mm_kernel, fmt, impl, dual, K, bk, out_fmt, out_impl, len(enc_tabs)
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, out_bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, out_n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fmt", "out_dtype", "out_fmt", "bm", "bn", "bk", "interpret",
        "decode_impl", "encode_impl",
    ),
)
def takum_matmul(
    x, w_bits, fmt, *, out_dtype=jnp.float32, out_fmt=None, bm=256, bn=256,
    bk=512, interpret=None, decode_impl=None, encode_impl=None,
):
    """x [M,K] f32/bf16 @ decode(w_bits [K,N] wire fmt) -> [M,N] out_dtype.

    ``fmt`` is a registered wire-format name or a bare takum width.
    ``out_fmt`` fuses the wire encode into the kernel epilogue: the output
    tile is encoded to packed ``out_fmt`` bits in-register before the HBM
    store (semantics: ``encode(matmul(...))``, see ``ref.fused_matmul_ref``)
    and the result dtype is the format's storage (``out_dtype`` is ignored).
    ``encode_impl`` picks the epilogue's codec strategy like ``decode_impl``.
    """
    interpret = interpret_default() if interpret is None else interpret
    name = wire_format(fmt).name
    impl = resolve_impl(decode_impl, name)
    out_fmt, out_impl = resolve_out_fmt(out_fmt, encode_impl)
    return _call(
        name, impl, False, x, w_bits, out_dtype, out_fmt, out_impl,
        bm, bn, bk, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def takum_matmul_ad(x, w_bits, fmt):
    """Differentiable wrapper: forward = dequant-matmul kernel; backward
    propagates to x only (``dx = g @ decode(w).T``, itself a dequant-matmul on
    the bit-transposed weights).  Quantised weights receive no cotangent —
    they are storage; master parameters are updated by the optimizer and
    re-encoded (see repro.quant).  Block-scaled formats are rejected: an
    interleaved payload has no bit-transpose (the scale bytes are bound to
    last-axis blocks) — mx weights dequantize at the use site instead."""
    if wire_format(fmt).is_block_scaled:
        raise ValueError(
            "takum_matmul_ad: block-scaled weights have no bit-transposed "
            "backward payload; dequantize mx weights at the use site"
        )
    return takum_matmul(x, w_bits, fmt)


def _takum_matmul_fwd(x, w_bits, fmt):
    # zero-size token carries x's dtype into the bwd rule (residuals must be arrays)
    return takum_matmul(x, w_bits, fmt), (w_bits, jnp.zeros((0,), x.dtype))


def _takum_matmul_bwd(fmt, res, g):
    w_bits, dtype_token = res
    dx = takum_matmul(g, w_bits.T, fmt)
    return dx.astype(dtype_token.dtype), None


takum_matmul_ad.defvjp(_takum_matmul_fwd, _takum_matmul_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fmt", "out_dtype", "out_fmt", "bm", "bn", "bk", "interpret",
        "decode_impl", "encode_impl",
    ),
)
def takum_dual_matmul(
    x_bits, w_bits, fmt, *, out_dtype=jnp.float32, out_fmt=None, bm=256,
    bn=256, bk=512, interpret=None, decode_impl=None, encode_impl=None,
):
    """decode(x_bits) @ decode(w_bits), both packed wire fmt (VDPPT analogue).

    ``out_fmt`` fuses the output wire encode into the epilogue (see
    :func:`takum_matmul`) — with ``out_fmt == fmt`` this is the fully
    bits-in/bits-out requantising GEMM: no f32 ever touches HBM.
    """
    interpret = interpret_default() if interpret is None else interpret
    name = wire_format(fmt).name
    impl = resolve_impl(decode_impl, name)
    out_fmt, out_impl = resolve_out_fmt(out_fmt, encode_impl)
    return _call(
        name, impl, True, x_bits, w_bits, out_dtype, out_fmt, out_impl,
        bm, bn, bk, interpret,
    )
