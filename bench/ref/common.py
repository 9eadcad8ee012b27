"""Numerics and layers shared by the plain references.

Everything computes in float32 with matmuls at ``Precision.HIGHEST``.  A
``Numerics`` says how operands are rounded before each matmul and how K/V
are stored; ``F32`` rounds nothing.  ``CONTROL`` is the reference computed
one precision step below what the configurations state: matmul operands in
OFP8 E4M3 and their gradients in E5M2, as FP8 training does (one step below
bf16 activations and 16-bit weights and gradients), and K/V in int4 (one
step below an 8-bit cache).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
#: tokens of the SSM recurrence between the states that training keeps
RECURRENCE_BLOCK = 64


def _same(x):
    return x


def e4m3(x):
    """Round to OFP8 E4M3 with one scale per tensor (amax to 448)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def int4(x):
    """Round to symmetric int4 with one scale per vector of the last axis."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / 7.0, 1.0)
    return jnp.clip(jnp.round(x / s), -8, 7) * s


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str
    act: Callable = _same  # activation operand of each matmul
    weight: Callable = _same  # weight operand of each matmul
    kv: Callable = _same  # K and V as a cache holds them


@jax.custom_vjp
def fp8(x):
    """FP8 as FP8 training computes: the operand rounded to E4M3, its
    gradient to E5M2, each with one scale per tensor."""
    return e4m3(x)


def _fp8_fwd(x):
    return e4m3(x), None


def _fp8_bwd(_, g):
    amax = jnp.max(jnp.abs(g))
    s = jnp.where(amax > 0, amax / 57344.0, 1.0)
    return ((g / s).astype(jnp.float8_e5m2).astype(jnp.float32) * s,)


fp8.defvjp(_fp8_fwd, _fp8_bwd)

F32 = Numerics("f32")
CONTROL = Numerics("fp8+int4kv", act=fp8, weight=fp8, kv=int4)


def mm(num: Numerics, x, w):
    """x [..., k] @ w [k, n] in float32 at HIGHEST."""
    return jnp.einsum("...k,kn->...n", num.act(x), num.weight(w), precision=HI)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, positions, theta):
    """Rotate halves: x [B, S, H, D], positions [S]."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def window_attention(q, k, v, window: int, block: int = 512):
    """Causal attention, each query over the ``window`` newest keys (0: all).

    q [B, S, H, hd], k/v [B, S, Kv, hd]; query head h reads KV head
    h // (H // Kv).  Computed in query blocks over only the key blocks the
    window reaches.
    """
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    g = H // Kv
    nb = -(-S // block)
    Sp = nb * block
    back = nb if window <= 0 else min(nb, -(-window // block))
    pad = ((0, 0), (back * block, Sp - S), (0, 0), (0, 0))
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(B, nb, block, Kv, g, hd)
    kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)
    span = (back + 1) * block

    def one(j):
        qj = qp[:, j]  # [B, block, Kv, g, hd]
        kj = lax.dynamic_slice_in_dim(kp, j * block, span, axis=1)
        vj = lax.dynamic_slice_in_dim(vp, j * block, span, axis=1)
        qpos = j * block + jnp.arange(block)
        kpos = (j - back) * block + jnp.arange(span)
        ok = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])
        if window > 0:
            ok &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.einsum("bqkgd,bskd->bkgqs", qj, kj, precision=HI) * hd ** -0.5
        s = jnp.where(ok[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vj, precision=HI)

    out = lax.map(one, jnp.arange(nb))  # [nb, B, block, Kv, g, hd]
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sp, H * hd)
    return out[:, :S]


def ssd_mixer(c, w, h, num: Numerics):
    """Mamba-2 mixer as its linear recurrence, token by token.

    h [B, S, d] -> [B, S, d].  Per head: H_t = exp(a dt_t) H_{t-1} +
    dt_t B_t (x) x_t, y_t = C_t . H_t + D x_t; then y * silu(z) through an
    RMS norm, and the output projection.  The depthwise causal conv runs
    over [x, B, C] before the split.
    """
    B, S, _ = h.shape
    d_in = w["out_proj"].shape[0]
    N = c["ssm_state"]
    hd = c["ssm_head_dim"]
    nh = d_in // hd
    kw = w["conv_w"].shape[0]
    zxbcdt = mm(num, h, w["in_proj"])
    z, xbc, dt = zxbcdt[..., :d_in], zxbcdt[..., d_in:2 * d_in + 2 * N], zxbcdt[..., 2 * d_in + 2 * N:]
    xp = jnp.pad(xbc, ((0, 0), (kw - 1, 0), (0, 0)))
    xc = sum(xp[:, i:i + S] * w["conv_w"][i] for i in range(kw)) + w["conv_b"]
    xc = jax.nn.silu(xc)
    x, Bm, Cm = xc[..., :d_in], xc[..., d_in:d_in + N], xc[..., d_in + N:]
    a = -jnp.exp(w["a_log"])
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [B, S, nh]
    xh = x.reshape(B, S, nh, hd)

    def step(Hs, t):
        x_t, b_t, c_t, dt_t = t
        Hs = Hs * jnp.exp(a * dt_t)[..., None, None] + jnp.einsum(
            "bh,bn,bhd->bhnd", dt_t, b_t, x_t, precision=HI)
        return Hs, jnp.einsum("bn,bhnd->bhd", c_t, Hs, precision=HI)

    # The recurrence runs token by token, in blocks of RECURRENCE_BLOCK
    # tokens that the backward pass recomputes: it keeps the state at each
    # block's start, not at every token (48 x 128 x 64 floats per row).
    # Zero tokens pad the last block; nothing before them depends on them.
    T = RECURRENCE_BLOCK
    n = -(-S // T)
    H0 = jnp.zeros((B, nh, N, hd), jnp.float32)
    xs = tuple(jnp.pad(jnp.moveaxis(t, 1, 0), ((0, n * T - S),) + ((0, 0),) * (t.ndim - 1))
               .reshape(n, T, *t.shape[:1], *t.shape[2:]) for t in (xh, Bm, Cm, dt))
    block = jax.checkpoint(lambda Hs, ts: lax.scan(step, Hs, ts, unroll=8))
    _, y = lax.scan(block, H0, xs)
    y = jnp.moveaxis(y.reshape(n * T, B, nh, hd)[:S], 0, 1) + w["D"][None, None, :, None] * xh
    y = y.reshape(B, S, d_in) * jax.nn.silu(z)
    y = rms_norm(y, w["norm_g"], 1e-5)
    return mm(num, y, w["out_proj"])
