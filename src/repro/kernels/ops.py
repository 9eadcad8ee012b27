"""Public jit'd entry points for the Pallas kernels (+ auto ref fallback).

Every op takes a *wire-format handle*: a registered name ('t8', 't16',
'e4m3', 'e5m2', 'bf16'), a :class:`~repro.core.formats.WireFormat`, or a
bare takum width (8/16 — the historical API).  The handle is normalised to
the canonical registry name before hitting the jitted kernels so aliases
share one compilation cache entry.

``use_kernels(False)`` routes every op through the pure-jnp reference —
useful inside large jitted programs (dry-run lowering) where interpret-mode
pallas calls would be slow, and as an A/B switch in benchmarks.

``decode_impl``/``encode_impl`` select the in-kernel codec strategy
("bits" = the family's branch-free codec, "lut" = VMEM table gather; None
picks the per-op, per-format measured winner in
``lut.DEFAULT_DECODE_IMPL``/``DEFAULT_ENCODE_IMPL``).  The reference
fallback ignores the knob (it defines the semantics both impls reproduce).

``encode``/``decode`` take any rank >= 1 (flatten-to-2D fast path onto the
element-wise codec kernels); the producer ops (``matmul``/``dual_matmul``/
``decode_attention``) take ``out_fmt=`` to fuse the output wire encode into
the kernel epilogue and return packed bits instead of f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.core.formats import kernel_wire_names, wire_format
from . import ref
from .lut import resolve_impl
from .takum_attention import takum_decode_attention
from .takum_codec import takum_decode_2d, takum_encode_2d
from .takum_matmul import takum_dual_matmul, takum_matmul

_USE_KERNELS = True


def _wire_bytes(*arrs) -> float:
    """Static wire-byte count of the packed-payload operands/results."""
    return float(sum(a.size * a.dtype.itemsize for a in arrs))


def _observed(op: str, fmt_name: str, call, *wire_arrs, out_is_wire=False):
    """Dispatch-layer observability (DESIGN.md §9, ``kernel.*`` namespace).

    Every dispatch runs inside ``jax.named_scope("kernel.<op>.<fmt>")``, in
    every run: metadata on the kernel's HLO ops that a profiler trace of
    the device attributes time to.  The counters add zero ops unless a
    :func:`repro.core.telemetry.capture` scope is active at trace time
    (asserted on the jaxpr in tests/test_obs.py).  Under a capture, every
    dispatch emits one ``kernel.calls.<op>.<fmt>`` counter and charges
    ``kernel.wire_bytes.<op>`` with the packed bytes it moved (the
    wire-side input operands, plus the packed output when ``out_is_wire``
    — i.e. ``encode`` and the fused ``out_fmt=`` producers).  Under
    shard_map the counts arrive once per device (multiplicity N).
    """
    with jax.named_scope(f"kernel.{op}.{fmt_name}"):
        out = call()
    if not telemetry.enabled():
        return out
    telemetry.emit(f"kernel.calls.{op}.{fmt_name}", 1.0)
    nbytes = _wire_bytes(*wire_arrs) + (_wire_bytes(out) if out_is_wire else 0.0)
    if nbytes:
        telemetry.emit(f"kernel.wire_bytes.{op}", nbytes)
    return out


def use_kernels(flag: bool) -> None:
    global _USE_KERNELS
    _USE_KERNELS = flag


def kernels_enabled() -> bool:
    return _USE_KERNELS


def supported_wire_formats() -> tuple[str, ...]:
    """Registered wire formats this dispatch layer can route to kernels.

    The CI bench step cross-checks this against the core registry: a format
    registered in :mod:`repro.core.formats` but missing here (or failing
    ``resolve_impl``) fails the perf-artifact validation.
    """
    out = []
    for name in kernel_wire_names():
        try:
            resolve_impl(None, name)
        except (KeyError, ValueError):  # pragma: no cover - registry drift
            continue
        out.append(name)
    return tuple(out)


def _name(fmt) -> str:
    return wire_format(fmt).name


def _as_2d(x):
    """ND -> 2D view for the element-wise codec kernels (flatten-to-2D).

    Returns ``(x2d, orig_shape_or_None)``; None means no reshape happened.
    1D becomes one padded row; >=3D collapses the leading dims onto the
    rows (the codec is element-wise, so any 2D cover is semantically
    identical — this is what keeps 3D/5D dist and KV-cache payloads on the
    kernel path instead of silently falling back to the jnp reference).
    """
    if x.ndim == 2:
        return x, None
    if x.ndim == 1:
        return x.reshape(1, -1), x.shape
    return x.reshape(-1, x.shape[-1]), x.shape


def _check_mx_payload(bits, name: str, what: str) -> None:
    """Loud shape validation for block-scaled *payload* operands.

    An mx payload interleaves one E8M0 scale byte with 32 element bytes per
    block — ``[scale | 32 elems]`` groups of 33 bytes on the last axis.  A
    last dim that is zero or not a multiple of 33 is a truncated or
    misaligned payload; decoding it would silently shear every scale byte
    into the element lanes, so it is rejected here (at the dispatch layer,
    before either the Pallas kernel or the jnp reference sees it).
    """
    wf = wire_format(name)
    if not wf.is_block_scaled or bits.ndim == 0:
        return
    L = bits.shape[-1]
    if L == 0 or L % 33:
        raise ValueError(
            f"{what} for block-scaled format {wf.name!r} has last dim {L}, "
            f"not a (nonzero) multiple of 33: a valid payload is whole "
            f"[scale|32 elems] 33-byte groups — this payload is truncated "
            f"or misaligned"
        )


def _check_mx_encode_input(x, name: str) -> None:
    """Block-scaled ``encode`` needs whole 32-element blocks on the last
    axis (callers that own the logical shape pad via
    ``quant.blockscale.pad_block``)."""
    wf = wire_format(name)
    if not wf.is_block_scaled or x.ndim == 0:
        return
    n = x.shape[-1]
    if n == 0 or n % 32:
        raise ValueError(
            f"encode to block-scaled format {wf.name!r} needs a last dim "
            f"that is a (nonzero) multiple of 32, got {n}: the container "
            f"quantises whole 32-element blocks (zero-pad with "
            f"quant.blockscale.pad_block)"
        )


def _kernel_fmt_ok(name: str) -> bool:
    """Formats the Pallas kernel codecs can move: wide takums (t32) are
    excluded — the kernel codec bodies only cover n <= 16 (``resolve_impl``
    rejects them loudly) — and stay on the jnp reference, which is exact
    for every registered width.  This also fixes the pre-PR silent
    corruption of 2D t32 payloads.  The block-scaled containers are
    first-class: their element codecs are the same 8-bit bodies and the
    payload ride-along is a reshape + scale multiply."""
    wf = wire_format(name)
    return not (wf.family == "takum" and wf.nbits > 16)


def _kernelable(x, name: str) -> bool:
    """Inputs the 2D codec kernels can take after flatten-to-2D."""
    return (
        _USE_KERNELS and x.ndim >= 1 and x.size > 0 and _kernel_fmt_ok(name)
    )


def _reshape_back(out, shape):
    """Undo the flatten-to-2D, keeping the codec's (possibly payload-width)
    last axis — for block-scaled formats ``encode`` grows and ``decode``
    shrinks the last dim by the 33/32 payload factor."""
    return out if shape is None else out.reshape(shape[:-1] + out.shape[-1:])


def encode(x, fmt, encode_impl=None):
    """float32 [...] -> packed wire-format bits (same shape; block-scaled
    formats return the interleaved payload, last dim n -> n/32*33, and
    require the last dim to be a multiple of 32 — callers that own the
    logical shape pad, see quant.blockscale.pad_block).

    Any rank >= 1 rides the Pallas codec kernel via the flatten-to-2D fast
    path; 0-d/empty inputs, wide takums (t32) and ``use_kernels(False)``
    fall back to the jnp reference (see ``_kernelable``).
    """
    name = _name(fmt)
    _check_mx_encode_input(x, name)

    def call():
        if _kernelable(x, name):
            x2, shape = _as_2d(x)
            out = takum_encode_2d(x2, name, encode_impl=encode_impl)
            return _reshape_back(out, shape)
        return ref.codec_encode_ref(x, name)

    return _observed("encode", name, call, out_is_wire=True)


def decode(bits, fmt, decode_impl=None):
    name = _name(fmt)
    _check_mx_payload(bits, name, "decode payload")

    def call():
        if _kernelable(bits, name):
            b2, shape = _as_2d(bits)
            out = takum_decode_2d(b2, name, decode_impl=decode_impl)
            return _reshape_back(out, shape)
        return ref.codec_decode_ref(bits, name)

    return _observed("decode", name, call, bits)


def matmul(x, w_bits, fmt, out_dtype=jnp.float32, decode_impl=None,
           out_fmt=None, encode_impl=None, **blocks):
    """x @ decode(w_bits): the dequant-in-kernel GEMM (VDPPT analogue).

    ``out_fmt`` fuses the output wire encode into the kernel epilogue
    (returns packed bits; semantics ``encode(matmul)`` — ref.fused_matmul_ref).
    """
    name = _name(fmt)
    _check_mx_payload(w_bits, name, "matmul w_bits")
    out_name = _name(out_fmt) if out_fmt is not None else None

    def call():
        if _USE_KERNELS and _kernel_fmt_ok(name) and (
            out_name is None or _kernel_fmt_ok(out_name)
        ):
            return takum_matmul(
                x, w_bits, name, out_dtype=out_dtype, decode_impl=decode_impl,
                out_fmt=out_name, encode_impl=encode_impl, **blocks
            )
        if out_fmt is not None:
            return ref.fused_matmul_ref(x, w_bits, name, out_name)
        return ref.takum_matmul_ref(x, w_bits, name, out_dtype=out_dtype)

    return _observed(
        "matmul", name, call, w_bits, out_is_wire=out_name is not None
    )


def dual_matmul(x_bits, w_bits, fmt, out_dtype=jnp.float32, decode_impl=None,
                out_fmt=None, encode_impl=None, **blocks):
    name = _name(fmt)
    _check_mx_payload(x_bits, name, "dual_matmul x_bits")
    _check_mx_payload(w_bits, name, "dual_matmul w_bits")
    out_name = _name(out_fmt) if out_fmt is not None else None

    def call():
        if _USE_KERNELS and _kernel_fmt_ok(name) and (
            out_name is None or _kernel_fmt_ok(out_name)
        ):
            return takum_dual_matmul(
                x_bits, w_bits, name, out_dtype=out_dtype,
                decode_impl=decode_impl, out_fmt=out_name,
                encode_impl=encode_impl, **blocks
            )
        if out_fmt is not None:
            return ref.fused_dual_matmul_ref(x_bits, w_bits, name, out_name)
        return ref.takum_dual_matmul_ref(
            x_bits, w_bits, name, out_dtype=out_dtype
        )

    return _observed(
        "dual_matmul", name, call, x_bits, w_bits,
        out_is_wire=out_name is not None,
    )


def decode_attention(q, k_bits, v_bits, fmt, decode_impl=None, out_fmt=None,
                     encode_impl=None, **kw):
    name = _name(fmt)
    _check_mx_payload(k_bits, name, "decode_attention k_bits")
    _check_mx_payload(v_bits, name, "decode_attention v_bits")
    out_name = _name(out_fmt) if out_fmt is not None else None

    def call():
        if _USE_KERNELS and _kernel_fmt_ok(name) and (
            out_name is None or _kernel_fmt_ok(out_name)
        ):
            return takum_decode_attention(
                q, k_bits, v_bits, name, decode_impl=decode_impl,
                out_fmt=out_name, encode_impl=encode_impl, **kw
            )
        if out_fmt is not None:
            return ref.fused_decode_attention_ref(
                q, k_bits, v_bits, name, out_name
            )
        return ref.decode_attention_ref(q, k_bits, v_bits, name)

    return _observed(
        "decode_attention", name, call, k_bits, v_bits,
        out_is_wire=out_name is not None,
    )
