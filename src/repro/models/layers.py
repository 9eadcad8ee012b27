"""Common model layers: RMSNorm, RoPE, SwiGLU, linear with quantised weights."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.quant.qtensor import QTensor


def linear(x, w):
    """x @ w in x's dtype with f32 accumulation (MXU semantics).

    w may be a raw array or a QTensor (takum-packed weights), dequantised at
    the use site; on TPU the fused Pallas dequant-matmul
    (repro.kernels.ops.matmul) replaces this pair — the HBM traffic (the
    roofline term) is identical: packed bits are read.  Keeping the operands
    in x.dtype (not promoting to w's f32) halves activation memory and uses
    the bf16 MXU path; accumulation stays f32 via preferred_element_type.
    """
    if isinstance(w, QTensor):
        w = w.dequantize(jnp.float32)
    return jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def rms_norm(x, gamma, eps: float = 1e-5):
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        s = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
        return ((xf * s) * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding.  x [..., S, H, D] (D even), positions [..., S]."""
    D = x.shape[-1]
    half = D // 2
    freqs = jnp.exp(
        -jnp.log(theta) * (jnp.arange(half, dtype=jnp.float32) / half)
    )  # [half]
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


def swiglu(x, wi, wg, wo):
    h = jax.nn.silu(linear(x, wg)) * linear(x, wi)
    return linear(h, wo)


def softcap(x, cap: float):
    return cap * jnp.tanh(x / cap) if cap and cap > 0 else x
