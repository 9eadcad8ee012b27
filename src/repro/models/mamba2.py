"""Mamba-2 (SSD, state-space duality — arXiv:2405.21060) in pure JAX.

Chunked SSD for training/prefill: within-chunk quadratic attention-like term
plus an inter-chunk state recurrence (lax.scan over chunks), O(S * Q) memory.
Decode: constant-size recurrent state per layer (ssm state [B, nh, N, hd]
or [B, nh, hd, N], see ``state_axes``, + conv tail [B, w-1, d_conv_in]).

Scalar-identity A per head (the SSD restriction), grouped B/C (G=1 group),
causal depthwise conv over [x, B, C] as in the reference implementation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist.actx import constrain


class MambaParams(NamedTuple):
    in_proj: jax.Array  # [d_model, 2*d_in + 2*N + nh]  (z, x, B, C, dt)
    conv_w: jax.Array  # [w, d_in + 2*N] depthwise
    conv_b: jax.Array  # [d_in + 2*N]
    a_log: jax.Array  # [nh]
    dt_bias: jax.Array  # [nh]
    D: jax.Array  # [nh]
    norm_g: jax.Array  # [d_in] gated RMSNorm weight
    out_proj: jax.Array  # [d_in, d_model]


def init_mamba(key, d_model: int, d_in: int, N: int, hd: int, w: int, dtype=jnp.float32):
    nh = d_in // hd
    ks = jax.random.split(key, 3)
    proj_out = 2 * d_in + 2 * N + nh
    return MambaParams(
        in_proj=(jax.random.normal(ks[0], (d_model, proj_out), dtype) * (d_model**-0.5)),
        conv_w=jax.random.normal(ks[1], (w, d_in + 2 * N), dtype) * 0.2,
        conv_b=jnp.zeros((d_in + 2 * N,), dtype),
        a_log=jnp.log(jnp.linspace(1.0, 16.0, nh).astype(dtype)),
        dt_bias=jnp.full((nh,), -4.6, dtype),  # softplus^-1(0.01)
        D=jnp.ones((nh,), dtype),
        norm_g=jnp.zeros((d_in,), dtype),
        out_proj=jax.random.normal(ks[2], (d_in, d_model), dtype) * (d_in**-0.5),
    )


def _split(pr: MambaParams, u, d_in: int, N: int, nh: int):
    with jax.named_scope("mamba.in_proj"):
        zxbcdt = u @ pr.in_proj
        z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * N], axis=-1)
        return z, xbc, dt


def _gated_norm(y, z, g, eps=1e-5):
    y = y * jax.nn.silu(z)
    s = lax.rsqrt(jnp.mean(jnp.square(y.astype(jnp.float32)), -1, keepdims=True) + eps)
    return (y.astype(jnp.float32) * s * (1.0 + g.astype(jnp.float32))).astype(y.dtype)


def mamba_forward(pr: MambaParams, u, *, N: int, hd: int, chunk: int, return_state: bool = False):
    """u [B, S, d_model] -> [B, S, d_model] (training/prefill, chunked SSD).

    ``return_state=True`` additionally returns the exact post-sequence
    ``MambaCache`` (conv tail + final SSM state) so prefill needs no replay.
    """
    B, S, _ = u.shape
    d_in = pr.out_proj.shape[0]
    nh = d_in // hd
    w = pr.conv_w.shape[0]

    z, xbc, dt = _split(pr, u, d_in, N, nh)
    with jax.named_scope("mamba.conv"):
        # causal depthwise conv over feature-grouped [x|B|C]
        pad = jnp.zeros((B, w - 1, xbc.shape[-1]), xbc.dtype)
        xp = jnp.concatenate([pad, xbc], axis=1)
        xc = sum(xp[:, i : i + S] * pr.conv_w[i] for i in range(w)) + pr.conv_b
        xc = constrain(jax.nn.silu(xc), "B", None, "M")
        x, Bm, Cm = jnp.split(xc, [d_in, d_in + N], axis=-1)

    with jax.named_scope("mamba.ssd"):
        y, H_final = _ssd(pr, x, Bm, Cm, dt, hd=hd, chunk=chunk)
        y = y.astype(u.dtype)
    with jax.named_scope("mamba.out"):
        out = _gated_norm(y, z, pr.norm_g) @ pr.out_proj
    if not return_state:
        return out
    # exact decode-ready state: conv tail = last w-1 *pre-conv* features
    if state_axes(N, hd) == "dn":
        H_final = jnp.swapaxes(H_final, -1, -2)
    cache = MambaCache(conv=xbc[:, S - (w - 1) :, :], ssm=H_final)
    return out, cache


def _ssd(pr: MambaParams, x, Bm, Cm, dt, *, hd: int, chunk: int):
    """Chunked SSD over x [B, S, d_in] with B/C [B, S, N] and raw dt
    [B, S, nh]: (y [B, S, d_in] f32 with the D skip, final state
    [B, nh, N, hd] f32)."""
    B, S, d_in = x.shape
    N = Bm.shape[-1]
    nh = d_in // hd
    a = -jnp.exp(pr.a_log.astype(jnp.float32))  # [nh], negative
    dt = jax.nn.softplus(dt.astype(jnp.float32) + pr.dt_bias)  # [B,S,nh]

    nc = S // chunk
    Q = chunk
    xh = x.reshape(B, nc, Q, nh, hd).astype(jnp.float32)
    Bc = Bm.reshape(B, nc, Q, N).astype(jnp.float32)
    Cc = Cm.reshape(B, nc, Q, N).astype(jnp.float32)
    dtc = dt.reshape(B, nc, Q, nh)
    adt = a * dtc  # [B,nc,Q,nh]
    cum = jnp.cumsum(adt, axis=2)  # within-chunk cumulative log-decay

    # intra-chunk ("diagonal block"): y_i += sum_{j<=i} C_i.B_j exp(cum_i-cum_j) dt_j x_j
    # masked before the exp: above the diagonal cum_i - cum_j > 0 grows with
    # the decay rate and overflows to inf, and a where() after the exp then
    # back-propagates inf * 0 = NaN
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [B,nc,Qi,Qj,nh]
    decay = jnp.exp(jnp.where(tri[None, None, :, :, None], seg, -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # [B,nc,Qi,Qj]
    gate = scores[..., None] * decay * dtc[:, :, None, :, :]  # [B,nc,Qi,Qj,nh]
    y_intra = jnp.einsum("bcijh,bcjhd->bcihd", gate, xh)

    # chunk summary states: S_c = sum_j exp(cum_last - cum_j) dt_j B_j (x) x_j
    last = cum[:, :, -1:, :]  # [B,nc,1,nh]
    w_j = jnp.exp(last - cum) * dtc  # [B,nc,Q,nh]
    S_c = jnp.einsum("bcjn,bcjh,bcjhd->bchnd", Bc, w_j, xh)  # [B,nc,nh,N,hd]

    # inter-chunk recurrence H_c = exp(sum adt_c) H_{c-1} + S_c
    chunk_decay = jnp.exp(last[:, :, 0, :])  # [B,nc,nh]

    def step(H, inp):
        dec, Sc = inp  # dec [B,nh], Sc [B,nh,N,hd]
        H_new = H * dec[..., None, None] + Sc
        return H_new, H  # emit state *before* this chunk

    H0 = jnp.zeros((B, nh, N, hd), jnp.float32)
    H_final, H_prev = lax.scan(
        step,
        H0,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(S_c, 1, 0)),
    )
    H_prev = jnp.moveaxis(H_prev, 0, 1)  # [B,nc,nh,N,hd] state entering chunk c

    # inter-chunk contribution: y_i += C_i . (exp(cum_i) H_prev)
    y_inter = jnp.einsum("bcin,bcih,bchnd->bcihd", Cc, jnp.exp(cum), H_prev)

    y = (y_intra + y_inter).reshape(B, S, nh, hd)
    y = y + pr.D[None, None, :, None] * x.reshape(B, S, nh, hd).astype(jnp.float32)
    return y.reshape(B, S, d_in), H_final


def state_axes(N: int, hd: int) -> str:
    """Order of the stored SSM state's last two axes, in einsum letters:
    ``"nd"`` is [.., N, hd], ``"dn"`` is [.., hd, N].  The larger of N and
    hd goes minor, where the TPU's (8, 128) tiles put it on the lanes: the
    decode update then works in the layout the state is stored in, with no
    relayout of the state on its way in or out.  A tie keeps ``"nd"``."""
    return "dn" if N > hd else "nd"


class MambaCache(NamedTuple):
    """Decode state of one layer: the conv tail [B, w-1, d_in + 2N] and the
    f32 SSM state, [B, nh, N, hd] or [B, nh, hd, N] by ``state_axes``."""

    conv: jax.Array
    ssm: jax.Array


def init_mamba_cache(B: int, d_in: int, N: int, hd: int, w: int, dtype=jnp.float32):
    """Zero decode state; the SSM state's last two axes in ``state_axes``
    order (N=128, hd=64 stores [B, nh, hd, N]; N=16, hd=64 [B, nh, N, hd])."""
    nh = d_in // hd
    last = (hd, N) if state_axes(N, hd) == "dn" else (N, hd)
    return MambaCache(
        conv=jnp.zeros((B, w - 1, d_in + 2 * N), dtype),
        ssm=jnp.zeros((B, nh) + last, jnp.float32),
    )


def mamba_decode_step(pr: MambaParams, u, cache: MambaCache, *, N: int, hd: int):
    """u [B, d_model] one token -> (y [B, d_model], new cache).  O(1) in S."""
    B, _ = u.shape
    d_in = pr.out_proj.shape[0]
    nh = d_in // hd
    w = pr.conv_w.shape[0]

    z, xbc, dt = _split(pr, u[:, None, :], d_in, N, nh)
    z, xbc, dt = z[:, 0], xbc[:, 0], dt[:, 0]

    with jax.named_scope("mamba.conv"):
        conv_in = jnp.concatenate([cache.conv, xbc[:, None, :]], axis=1)  # [B,w,*]
        xc = jnp.einsum("bwf,wf->bf", conv_in, pr.conv_w) + pr.conv_b
        xc = jax.nn.silu(xc)
        x, Bm, Cm = jnp.split(xc, [d_in, d_in + N], axis=-1)

    with jax.named_scope("mamba.ssm_update"):
        a = -jnp.exp(pr.a_log.astype(jnp.float32))
        dtv = jax.nn.softplus(dt.astype(jnp.float32) + pr.dt_bias)  # [B,nh]
        dec = jnp.exp(a * dtv)  # [B,nh]

        xhead = x.reshape(B, nh, hd).astype(jnp.float32)
        st = "bh" + state_axes(N, hd)
        upd = jnp.einsum(f"bn,bh,bhd->{st}", Bm.astype(jnp.float32), dtv, xhead)
        ssm = cache.ssm * dec[..., None, None] + upd
        y = jnp.einsum(f"bn,{st}->bhd", Cm.astype(jnp.float32), ssm)
        y = y + pr.D[None, :, None] * xhead
        y = y.reshape(B, d_in).astype(u.dtype)
    with jax.named_scope("mamba.out"):
        out = _gated_norm(y, z, pr.norm_g) @ pr.out_proj
    return out, MambaCache(conv=conv_in[:, 1:], ssm=ssm)
