"""Pallas TPU kernel: vectorised wire-format encode/decode (the VCVT family).

Element-wise codec over 2D tiles for any registered
:class:`~repro.core.formats.WireFormat` (t8/t16 takum, OFP8 E4M3/E5M2,
bf16, and the block-scaled mx* containers).  BlockSpec keeps one
(block_rows, block_cols) tile of input + output in VMEM; the body is either
the family's branch-free bit manipulation (shared <=12-bit header decoder
for takum, paper §I; field unpack for OFP8; shift-bitcast for bf16) or the
table-driven path (one VMEM gather per element for decode, two gathers for
the tabulated encodes — the 8-bit exponent-byte pairs or the two-level
takum16 scheme) feeding the VPU — selectable per call via
``decode_impl``/``encode_impl``, resting on the per-op measured winners in
``lut.DEFAULT_DECODE_IMPL``/``DEFAULT_ENCODE_IMPL``.

Block-scaled formats move *interleaved payloads*: 33 uint8 bytes per
32-element block (scale byte + element bytes, :mod:`repro.quant.blockscale`),
so the payload axis is 33/32 the element axis.  Mosaic cannot split a
tile's 128 lanes into 33-byte groups, so XLA takes the container apart
around the kernel: decode gets element bits plus one scale byte per element
(:func:`~repro.quant.blockscale.split_payload`), encode gets the tile plus
its per-element scale bytes and returns element bits that XLA interleaves
with the scales.  The impl knob selects the *element* codec inside the
container; the E8M0 scale multiply is the same few ops either way.  The
element axis must be a
multiple of 32 — callers that own the logical shape pad (QTensor, the
collectives); ``kernels.ops`` falls back to the jnp reference and raises
the same alignment error there.

Arbitrary (R, C) shapes are supported: the grid is cdiv-padded and edge
tiles need no masking — the codec is element-wise (block-scaled: per
whole-block), so garbage padding lanes only produce garbage outputs that
the clipped store drops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import wire_format
from repro.quant import blockscale
from .common import choose_block, interpret_default, sublane_align
from .lut import (
    decode_table_operand,
    encode_table_operands,
    resolve_impl,
    wire_decode_fn,
    wire_encode_fn,
)


def _decode_kernel(fmt, impl, *refs):
    # the decode table leads for the LUT impl; block-scaled formats take
    # (bits, per-element scale bytes) tiles
    tab_ref = refs[0] if impl == "lut" else None
    in_refs, o_ref = refs[int(impl == "lut") : -1], refs[-1]
    decode = wire_decode_fn(fmt, impl, tab_ref)
    o_ref[...] = decode(*(r[...] for r in in_refs))


def _encode_kernel(fmt, impl, ntab, *refs):
    # table operands lead: (meta, thr) 8-bit / (meta, sub) takum16;
    # block-scaled formats take (x, per-element scale bytes) tiles
    tabs, in_refs, o_ref = refs[:ntab], refs[ntab:-1], refs[-1]
    enc = wire_encode_fn(fmt, impl, tabs)
    o_ref[...] = enc(*(r[...] for r in in_refs)).astype(o_ref.dtype)


def _blocks(R, C, block_rows, block_cols, packed_dtype):
    br = choose_block(R, block_rows, sublane_align(packed_dtype))
    bc = choose_block(C, block_cols, 128)
    return br, bc, (pl.cdiv(R, br), pl.cdiv(C, bc))


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "block_rows", "block_cols", "interpret", "decode_impl"),
)
def takum_decode_2d(
    bits, fmt, *, block_rows=256, block_cols=512, interpret=None, decode_impl=None
):
    """[R, C] packed wire format -> [R, C] float32.

    ``fmt`` is a registered wire-format name or a bare takum width
    (8 -> t8, 16 -> t16; the historical API).  For block-scaled formats the
    input is the interleaved payload [R, C/32*33] and C is recovered from
    the payload width.
    """
    interpret = interpret_default() if interpret is None else interpret
    wf = wire_format(fmt)
    name = wf.name
    impl = resolve_impl(decode_impl, name)
    # block-scaled payloads enter the kernel split (XLA takes the container
    # apart: Mosaic cannot split lanes into 33-byte groups)
    args = list(blockscale.split_payload(bits)) if wf.is_block_scaled else [bits]
    R, C = args[0].shape
    br, bc, grid = _blocks(R, C, block_rows, block_cols, args[0].dtype)
    in_specs = [pl.BlockSpec((br, bc), lambda i, j: (i, j)) for _ in args]
    if impl == "lut":
        tab = decode_table_operand(name)
        in_specs.insert(0, pl.BlockSpec(tab.shape, lambda i, j: (0, 0)))
        args.insert(0, tab)
    return pl.pallas_call(
        functools.partial(_decode_kernel, name, impl),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=("fmt", "block_rows", "block_cols", "interpret", "encode_impl"),
)
def takum_encode_2d(
    x, fmt, *, block_rows=256, block_cols=512, interpret=None, encode_impl=None
):
    """[R, C] float32 -> [R, C] packed wire format (uint8/uint16); for
    block-scaled formats the output is the interleaved payload
    [R, C/32*33] and C must be a multiple of 32."""
    interpret = interpret_default() if interpret is None else interpret
    wf = wire_format(fmt)
    impl = resolve_impl(encode_impl, wf.name, op="encode")
    R, C = x.shape
    args = [x]
    if wf.is_block_scaled:
        if C % blockscale.BLOCK:
            raise ValueError(
                f"block-scaled encode needs a 32-multiple column count, got {C}"
            )
        # the per-block scale bytes come from XLA (a segmented absmax), the
        # element encode runs in the kernel, and XLA interleaves the payload
        scales = blockscale.block_scale_bytes(x, wf)
        args.append(blockscale.expand_scales(scales))
        out_dtype = wf.elem.storage
    else:
        out_dtype = wf.storage
    br, bc, grid = _blocks(R, C, block_rows, block_cols, out_dtype)
    in_specs = [pl.BlockSpec((br, bc), lambda i, j: (i, j)) for _ in args]
    tabs = encode_table_operands(wf.name) if impl == "lut" else ()
    in_specs = [pl.BlockSpec(t.shape, lambda i, j: (0, 0)) for t in tabs] + in_specs
    out = pl.pallas_call(
        functools.partial(_encode_kernel, wf.name, impl, len(tabs)),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
    )(*tabs, *args)
    return blockscale.pack_payload(scales, out) if wf.is_block_scaled else out
