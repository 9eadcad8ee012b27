"""Unified decoder model covering all assigned architecture families.

families:
  dense   — llama3-8b, llama3.2-3b, granite-34b (MQA), gemma2-2b (alternating
            local/global SWA + logit softcaps + post-norms)
  moe     — kimi-k2 (384e top-8 + shared expert), dbrx (16e top-4)
  audio   — musicgen-large (decoder over EnCodec tokens; frontend stubbed to
            token ids per the task spec)
  vlm     — llama-3.2-vision-90b (cross-attention onto stub patch embeddings
            every k-th layer)
  hybrid  — hymba-1.5b (parallel attention + mamba heads per layer, SWA)
  ssm     — mamba2-780m (attention-free; layers = SSD mixer only)

Layers are parameter-stacked and driven by ``lax.scan`` (small HLO, fast
compile — essential for the 512-device dry-run on one CPU core).  KV caches
are stored in the configured quantisation format (takum8/16 bit patterns or
bf16) — the paper's uniform-format thesis applied to the serving path.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import telemetry
from repro.core.takum import takum_decode
from repro.dist import faults
from repro.dist.actx import constrain
from repro.core.formats import count_specials, wire_format
from repro.kernels.lut import encode_jnp_fast
from repro.quant.policy import is_takum, takum_width
from .attention import flash_attention
from .config import ModelConfig
from .layers import linear, rms_norm, rope, softcap, swiglu
from .mamba2 import (
    MambaCache,
    MambaParams,
    init_mamba,
    init_mamba_cache,
    mamba_decode_step,
    mamba_forward,
)
from .moe import moe_block

_EMPTY = jnp.zeros((0,), jnp.float32)


def _chunk_of(S: int, want: int) -> int:
    c = min(S, want)
    while S % c:
        c -= 1
    return c


def _ssm_d_in(cfg: ModelConfig) -> int:
    return cfg.ssm_expand * cfg.d_model if cfg.family == "ssm" else cfg.d_model


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def _dense_init(key, shape, scale, dtype):
    return jax.random.normal(key, shape, dtype) * scale


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    hd = cfg.resolved_head_dim if cfg.num_heads else 0
    keys = iter(jax.random.split(key, 64))
    p: dict[str, Any] = {"embed": _dense_init(next(keys), (V, d), d**-0.5, dtype)}

    def attn_params(k, n_layers):
        ks = jax.random.split(k, 4)
        H, Kv = cfg.num_heads, cfg.num_kv_heads
        return {
            "wq": _dense_init(ks[0], (n_layers, d, H * hd), d**-0.5, dtype),
            "wk": _dense_init(ks[1], (n_layers, d, Kv * hd), d**-0.5, dtype),
            "wv": _dense_init(ks[2], (n_layers, d, Kv * hd), d**-0.5, dtype),
            "wo": _dense_init(ks[3], (n_layers, H * hd, d), (H * hd) ** -0.5, dtype),
        }

    def mlp_params(k, n_layers, dff):
        ks = jax.random.split(k, 3)
        return {
            "wi": _dense_init(ks[0], (n_layers, d, dff), d**-0.5, dtype),
            "wg": _dense_init(ks[1], (n_layers, d, dff), d**-0.5, dtype),
            "wo": _dense_init(ks[2], (n_layers, dff, d), dff**-0.5, dtype),
        }

    layers: dict[str, Any] = {"ln1": jnp.zeros((L, d), dtype)}
    if cfg.family != "ssm":
        layers["ln2"] = jnp.zeros((L, d), dtype)
        layers["attn"] = attn_params(next(keys), L)
    if cfg.alt_local_global:  # gemma2 post-norms
        layers["ln1_post"] = jnp.zeros((L, d), dtype)
        layers["ln2_post"] = jnp.zeros((L, d), dtype)

    if cfg.family == "moe":
        E, f = cfg.num_experts, cfg.d_ff
        ks = jax.random.split(next(keys), 4)
        layers["moe"] = {
            "router": _dense_init(ks[0], (L, d, E), d**-0.5, jnp.float32),
            "wi": _dense_init(ks[1], (L, E, d, f), d**-0.5, dtype),
            "wg": _dense_init(ks[2], (L, E, d, f), d**-0.5, dtype),
            "wo": _dense_init(ks[3], (L, E, f, d), f**-0.5, dtype),
        }
        if cfg.num_shared_experts:
            fs = cfg.d_ff * cfg.num_shared_experts
            ks = jax.random.split(next(keys), 3)
            layers["moe"]["wi_s"] = _dense_init(ks[0], (L, d, fs), d**-0.5, dtype)
            layers["moe"]["wg_s"] = _dense_init(ks[1], (L, d, fs), d**-0.5, dtype)
            layers["moe"]["wo_s"] = _dense_init(ks[2], (L, fs, d), fs**-0.5, dtype)
    elif cfg.family in ("dense", "audio", "vlm", "hybrid"):
        layers["mlp"] = mlp_params(next(keys), L, cfg.d_ff)

    if cfg.family in ("ssm", "hybrid"):
        d_in = _ssm_d_in(cfg)
        lkeys = jax.random.split(next(keys), L)
        layers["ssm"] = jax.vmap(
            lambda k: init_mamba(
                k, d, d_in, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_conv_width, dtype
            )
        )(lkeys)

    p["layers"] = layers
    p["final_norm"] = jnp.zeros((d,), dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense_init(next(keys), (d, V), d**-0.5, dtype)

    if cfg.family == "vlm":
        Lc = L // cfg.cross_attn_every
        cross = attn_params(next(keys), Lc)
        cross["ln"] = jnp.zeros((Lc, d), dtype)
        cross["gate"] = jnp.zeros((Lc,), dtype)
        p["cross_layers"] = cross
        p["media_proj"] = _dense_init(next(keys), (cfg.media_d, d), cfg.media_d**-0.5, dtype)
    return p


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _self_attn(cfg: ModelConfig, lp, x, positions, window):
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = constrain(linear(x, lp["wq"]).reshape(B, S, H, hd), "B", None, "M", None)
    k = constrain(linear(x, lp["wk"]).reshape(B, S, Kv, hd), "B", None, "M", None)
    v = constrain(linear(x, lp["wv"]).reshape(B, S, Kv, hd), "B", None, "M", None)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    out = flash_attention(
        q, k, v, window, True, cfg.attn_softcap, _chunk_of(S, cfg.attn_chunk_kv), 0
    )
    return linear(out.reshape(B, S, H * hd), lp["wo"]), (k, v)


def _cross_attn(cfg: ModelConfig, cp, x, media):
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    M = media.shape[1]
    q = linear(x, cp["wq"]).reshape(B, S, H, hd)
    k = linear(media, cp["wk"]).reshape(B, M, Kv, hd)
    v = linear(media, cp["wv"]).reshape(B, M, Kv, hd)
    out = flash_attention(q, k, v, 0, False, 0.0, _chunk_of(M, cfg.attn_chunk_kv), 0)
    return linear(out.reshape(B, S, H * hd), cp["wo"])


def _layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    L = cfg.num_layers
    if cfg.alt_local_global:
        return jnp.asarray([cfg.sliding_window if i % 2 == 0 else 0 for i in range(L)])
    return jnp.full((L,), cfg.sliding_window)


def _mlp_or_moe(cfg: ModelConfig, params_l, h2):
    if cfg.family == "moe":
        mp = params_l["moe"]
        shared = (mp["wi_s"], mp["wg_s"], mp["wo_s"]) if cfg.num_shared_experts else None
        return moe_block(
            h2, mp["router"], mp["wi"], mp["wg"], mp["wo"], shared,
            top_k=cfg.experts_per_token, capacity_factor=cfg.moe_capacity_factor,
        )
    m = params_l["mlp"]
    return swiglu(h2, m["wi"], m["wg"], m["wo"]), jnp.float32(0.0)


def _block(cfg: ModelConfig, params_l, window, x, positions, collect: bool):
    """One decoder layer.  Returns (x, aux, cache_bits) — cache_bits is a
    tuple of scan-stackable arrays (empty placeholders when not collected)."""
    aux = jnp.float32(0.0)
    kv_k = kv_v = conv = ssm = _EMPTY
    in_dtype = x.dtype

    if cfg.family == "ssm":
        h = rms_norm(x, params_l["ln1"], cfg.norm_eps)
        if collect:
            y, mc = mamba_forward(
                params_l["ssm"], h, N=cfg.ssm_state, hd=cfg.ssm_head_dim,
                chunk=_chunk_of(h.shape[1], cfg.ssm_chunk), return_state=True,
            )
            conv, ssm = mc.conv, mc.ssm
        else:
            y = mamba_forward(
                params_l["ssm"], h, N=cfg.ssm_state, hd=cfg.ssm_head_dim,
                chunk=_chunk_of(h.shape[1], cfg.ssm_chunk),
            )
        return constrain((x + y).astype(in_dtype), "B", None, None), aux, (kv_k, kv_v, conv, ssm)

    h = rms_norm(x, params_l["ln1"], cfg.norm_eps)
    with jax.named_scope("attn"):
        attn_out, (k, v) = _self_attn(cfg, params_l["attn"], h, positions, window)
    if collect:
        kv_k, kv_v = k, v
    if cfg.family == "hybrid":
        if collect:
            ssm_out, mc = mamba_forward(
                params_l["ssm"], h, N=cfg.ssm_state, hd=cfg.ssm_head_dim,
                chunk=_chunk_of(h.shape[1], cfg.ssm_chunk), return_state=True,
            )
            conv, ssm = mc.conv, mc.ssm
        else:
            ssm_out = mamba_forward(
                params_l["ssm"], h, N=cfg.ssm_state, hd=cfg.ssm_head_dim,
                chunk=_chunk_of(h.shape[1], cfg.ssm_chunk),
            )
        attn_out = 0.5 * (attn_out + ssm_out)
    if cfg.alt_local_global:
        attn_out = rms_norm(attn_out, params_l["ln1_post"], cfg.norm_eps)
    x = x + attn_out

    h2 = rms_norm(x, params_l["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        mlp_out, aux = _mlp_or_moe(cfg, params_l, h2)
    if cfg.alt_local_global:
        mlp_out = rms_norm(mlp_out, params_l["ln2_post"], cfg.norm_eps)
    x = constrain((x + mlp_out).astype(in_dtype), "B", None, None)
    return x, aux, (kv_k, kv_v, conv, ssm)


def forward(cfg: ModelConfig, params, tokens, media=None, *, collect: bool = False):
    """tokens [B, S] -> (logits [B, S, V], aux, cache_bits or None).

    ``collect=True`` additionally emits per-layer KV (and SSM state) stacked
    on a leading L axis — the prefill path.
    """
    B, S = tokens.shape
    adt = jnp.bfloat16 if cfg.quant.activations == "bf16" else jnp.float32
    with jax.named_scope("embed"):
        x = constrain(params["embed"][tokens].astype(adt), "B", None, None)
        if cfg.alt_local_global:
            x = x * (cfg.d_model**0.5)  # gemma2 embedding scaling
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    windows = _layer_windows(cfg)

    media_emb = None
    if cfg.family == "vlm":
        assert media is not None, "vlm needs media embeddings"
        media_emb = (media.astype(adt) @ params["media_proj"].astype(adt))

    layers = params["layers"]
    L = cfg.num_layers

    def layer_step(carry, xs):
        x, aux = carry
        params_l, window = xs
        x, aux_l, cache_bits = _block(cfg, params_l, window, x, positions, collect)
        return (x, aux + aux_l), cache_bits

    step = jax.checkpoint(layer_step) if cfg.remat == "block" else layer_step

    if cfg.family == "vlm":
        kk = cfg.cross_attn_every
        Lc = L // kk
        self_stacked = jax.tree.map(lambda a: a.reshape((Lc, kk) + a.shape[1:]), layers)
        win_stacked = windows.reshape(Lc, kk)
        cross = params["cross_layers"]

        def vlm_block(carry, xs):
            x, aux = carry
            self_p, wins, cross_p = xs
            (x, aux), cache_bits = lax.scan(step, (x, aux), (self_p, wins))
            h = rms_norm(x, cross_p["ln"], cfg.norm_eps)
            with jax.named_scope("attn"):
                gate = jnp.tanh(cross_p["gate"]).astype(x.dtype)
                x = (x + gate * _cross_attn(cfg, cross_p, h, media_emb)).astype(h.dtype)
            return (x, aux), cache_bits

        vb = jax.checkpoint(vlm_block) if cfg.remat == "block" else vlm_block
        with jax.named_scope("layers"):
            (x, aux), cache_bits = lax.scan(
                vb, (x, jnp.float32(0.0)), (self_stacked, win_stacked, cross))
        if collect:  # [Lc, kk, ...] -> [L, ...]
            cache_bits = jax.tree.map(
                lambda a: a.reshape((Lc * kk,) + a.shape[2:]) if a.ndim >= 2 else a,
                cache_bits,
            )
    else:
        with jax.named_scope("layers"):
            (x, aux), cache_bits = lax.scan(step, (x, jnp.float32(0.0)), (layers, windows))

    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        logits = constrain(softcap(logits, cfg.logit_softcap), "B", None, "M")
    return logits, aux, (cache_bits if collect else None)


def loss_fn(cfg: ModelConfig, params, batch, *, aux_weight: float = 0.01):
    """Next-token cross-entropy (+ MoE balance loss).

    The gold-logit gather is a one-hot contraction, NOT take_along_axis:
    under a vocab-sharded (TP) logits layout a gather would make GSPMD
    all-gather the full [B,S,V] tensor per device (observed: 125 GB/device
    on llama3.2-3b train_4k); the contraction reduces shard-locally."""
    tokens = batch["tokens"]
    logits, aux, _ = forward(cfg, params, tokens, media=batch.get("media"))
    with jax.named_scope("head"):
        tgt = tokens[:, 1:]
        lg = logits[:, :-1]
        logz = jax.scipy.special.logsumexp(lg, axis=-1)
        oh = jax.nn.one_hot(tgt, lg.shape[-1], dtype=lg.dtype)
        gold = jnp.einsum("bsv,bsv->bs", lg, oh)
        ce = (logz - gold).mean()
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving: quantised KV cache, prefill + decode
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    k: Any  # [L, B, S, Hkv, hd] cache-format (takum bits or bf16/f32)
    v: Any
    pos: Any  # [] int32
    conv: Any = _EMPTY  # [L, B, w-1, feat] (ssm/hybrid)
    ssm: Any = _EMPTY  # [L, B, nh, N, hd] or [L, B, nh, hd, N] f32 (mamba2.state_axes)


def _encode_cache(cfg, x):
    """KV entries -> cache storage, per ``quant.kv_cache``: takum/OFP8 pack
    to wire bits (e4m3 KV caches ride the registry), the block-scaled mx*
    formats pack to the interleaved scale+bits payload (head dim zero-padded
    to a 32-multiple; the payload axis is hd/32*33 bytes), IEEE stays float.

    The append is encoded *at the producer* — the fast per-format encode
    (table path for takum, bit-identical to the codec; branch-free packer
    for OFP8) runs on the fresh K/V projections right where they are
    computed, instead of a second codec pass over the cache.

    This is also a fault-containment surface (DESIGN.md §8): appended
    payloads take the active :mod:`repro.dist.faults` corruption (modelling
    HBM/cache bit rot), and under a telemetry capture each append counts
    its special codes (``kv.specials.<fmt>``) — poisoned K/V projections
    show up here one decode step before they show up as NaN logits."""
    with jax.named_scope("kv.encode"):
        fmt = cfg.quant.kv_cache
        wf = wire_format(fmt)
        if wf.is_block_scaled:
            from repro.quant import blockscale

            bits = encode_jnp_fast(
                blockscale.pad_block(x.astype(jnp.float32)), wf.name
            )
        elif wf.family in ("takum", "ofp8"):
            bits = encode_jnp_fast(x.astype(jnp.float32), wf.name)
        else:
            bits = x.astype(jnp.bfloat16 if fmt == "bf16" else jnp.float32)
            if fmt == "f32":
                return bits  # exact storage: nothing to corrupt or count
        bits = faults.corrupt_payload(bits, wf.name)
        if telemetry.enabled():
            telemetry.emit(f"kv.appends.{wf.name}", jnp.float32(1))
            telemetry.emit(f"kv.specials.{wf.name}", count_specials(bits, wf.name))
            telemetry.emit(
                f"kv.bytes.{wf.name}", float(bits.size * bits.dtype.itemsize)
            )
        return bits


def _decode_cache(cfg, bits, hd: int | None = None):
    """Cache storage -> f32.  ``hd`` is the logical head dim, needed to
    slice the zero padding off a block-scaled payload."""
    with jax.named_scope("kv.decode"):
        fmt = cfg.quant.kv_cache
        wf = wire_format(fmt)
        if wf.is_block_scaled:
            from repro.kernels.lut import decode_jnp_fast

            out = decode_jnp_fast(bits, wf.name)
            return out if hd is None else out[..., :hd]
        if is_takum(fmt):
            return takum_decode(bits, takum_width(fmt))
        if wf.family == "ofp8":
            return wf.decode_jnp(bits)
        return bits.astype(jnp.float32)


def _cache_dtype(cfg):
    fmt = cfg.quant.kv_cache
    wf = wire_format(fmt)
    if is_takum(fmt) or wf.family == "ofp8" or wf.is_block_scaled:
        return wf.storage
    return jnp.bfloat16 if fmt == "bf16" else jnp.float32


def _cache_feat(cfg, hd: int) -> int:
    """Stored feature width of one KV entry: the head dim, or the
    interleaved-payload width for a block-scaled cache format."""
    wf = wire_format(cfg.quant.kv_cache)
    if wf.is_block_scaled:
        from repro.quant import blockscale

        return blockscale.payload_len(hd)
    return hd


def init_cache(cfg: ModelConfig, B: int, S: int) -> KVCache:
    L, Kv, hd = cfg.num_layers, max(cfg.num_kv_heads, 1), cfg.resolved_head_dim
    conv, ssm = _EMPTY, _EMPTY
    if cfg.family in ("ssm", "hybrid"):
        d_in = _ssm_d_in(cfg)
        c0 = init_mamba_cache(B, d_in, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_conv_width)
        conv = jnp.zeros((L,) + c0.conv.shape, c0.conv.dtype)
        ssm = jnp.zeros((L,) + c0.ssm.shape, c0.ssm.dtype)
    if cfg.family == "ssm":
        k = v = jnp.zeros((L, B, 0, 1, 1), _cache_dtype(cfg))
    else:
        k = v = jnp.zeros((L, B, S, Kv, _cache_feat(cfg, hd)), _cache_dtype(cfg))
    return KVCache(k=k, v=v, pos=jnp.int32(0), conv=conv, ssm=ssm)


def prefill(cfg: ModelConfig, params, tokens, media=None, *, cache_len: int | None = None):
    """Full forward emitting a quantised KV cache.  Returns (logits[B,V], cache).

    ``cache_len`` > S pre-allocates room for subsequent decode steps.
    """
    B, S = tokens.shape
    total = cache_len or S
    logits, _, bits = forward(cfg, params, tokens, media=media, collect=True)
    kv_k, kv_v, conv, ssm = bits
    cache = init_cache(cfg, B, total)
    if cfg.family != "ssm":
        k_enc = _encode_cache(cfg, kv_k)  # [L, B, S, Kv, hd]
        v_enc = _encode_cache(cfg, kv_v)
        cache = cache._replace(
            k=lax.dynamic_update_slice(cache.k, k_enc, (0, 0, 0, 0, 0)),
            v=lax.dynamic_update_slice(cache.v, v_enc, (0, 0, 0, 0, 0)),
        )
    if cfg.family in ("ssm", "hybrid"):
        cache = cache._replace(conv=conv, ssm=ssm)
    return logits[:, -1], cache._replace(pos=jnp.int32(S))


def decode_step(cfg: ModelConfig, params, token, cache: KVCache, media=None):
    """One decode step.  token [B] -> (logits [B, V], updated cache).

    Attention reads the *quantised* cache, dequantised on the fly (on TPU the
    Pallas takum flash-decode kernel; here the jnp reference semantics)."""
    B = token.shape[0]
    d = cfg.d_model
    adt = jnp.bfloat16 if cfg.quant.activations == "bf16" else jnp.float32
    with jax.named_scope("embed"):
        x = params["embed"][token].astype(adt)
        if cfg.alt_local_global:
            x = x * (d**0.5)
    pos = cache.pos
    windows = _layer_windows(cfg)
    L = cfg.num_layers
    H, Kv, hd = cfg.num_heads or 0, max(cfg.num_kv_heads, 1), cfg.resolved_head_dim

    media_emb = None
    if cfg.family == "vlm":
        media_emb = media.astype(adt) @ params["media_proj"].astype(adt)

    def attn_decode(lp, h, k_layer, v_layer, window):
        # h [B, d] single position
        q = linear(h[:, None], lp["wq"]).reshape(B, 1, H, hd)
        q = rope(q, jnp.full((B, 1), pos), cfg.rope_theta)
        k_new = rope(
            linear(h[:, None], lp["wk"]).reshape(B, 1, Kv, hd),
            jnp.full((B, 1), pos), cfg.rope_theta,
        )
        v_new = linear(h[:, None], lp["wv"]).reshape(B, 1, Kv, hd)
        k_layer = lax.dynamic_update_slice(k_layer, _encode_cache(cfg, k_new), (0, pos, 0, 0))
        v_layer = lax.dynamic_update_slice(v_layer, _encode_cache(cfg, v_new), (0, pos, 0, 0))
        k_layer = constrain(k_layer, "B", "M", None, None)
        v_layer = constrain(v_layer, "B", "M", None, None)
        kf = _decode_cache(cfg, k_layer, hd)  # [B, S, Kv, hd] f32
        vf = _decode_cache(cfg, v_layer, hd)
        S = kf.shape[1]
        kpos = jnp.arange(S)
        valid = kpos <= pos
        valid = jnp.where(window > 0, valid & ((pos - kpos) < window), valid)
        g = H // Kv
        kk = jnp.repeat(kf, g, axis=2)
        vv = jnp.repeat(vf, g, axis=2)
        logits = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), kk) * (hd**-0.5)
        logits = softcap(logits, cfg.attn_softcap)
        logits = jnp.where(valid[None, None, None, :], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhqs,bshd->bqhd", p, vv).reshape(B, 1, H * hd).astype(h.dtype)
        return linear(o, lp["wo"])[:, 0], k_layer, v_layer

    def mamba(params_l, h, conv, ssm, i):
        # layer i's slice of the stacked state, updated in place in the stack
        y, mc = mamba_decode_step(
            params_l["ssm"], h,
            MambaCache(lax.dynamic_index_in_dim(conv, i, keepdims=False),
                       lax.dynamic_index_in_dim(ssm, i, keepdims=False)),
            N=cfg.ssm_state, hd=cfg.ssm_head_dim,
        )
        return (y, lax.dynamic_update_index_in_dim(conv, mc.conv, i, 0),
                lax.dynamic_update_index_in_dim(ssm, mc.ssm, i, 0))

    def layer_step(carry, xs):
        # the stacked conv/SSM state rides in the carry (ssm, hybrid), so the
        # loop updates the donated cache in place; K/V go through xs -> ys
        x, conv, ssm = carry
        in_dtype = x.dtype
        params_l, window, k_l, v_l, i = xs
        h = rms_norm(x, params_l["ln1"], cfg.norm_eps)
        if cfg.family == "ssm":
            y, conv, ssm = mamba(params_l, h, conv, ssm, i)
            return ((x + y).astype(in_dtype), conv, ssm), (k_l, v_l)
        with jax.named_scope("attn"):
            attn_out, k_l, v_l = attn_decode(params_l["attn"], h, k_l, v_l, window)
        if cfg.family == "hybrid":
            y_ssm, conv, ssm = mamba(params_l, h, conv, ssm, i)
            attn_out = 0.5 * (attn_out + y_ssm)
        if cfg.alt_local_global:
            attn_out = rms_norm(attn_out, params_l["ln1_post"], cfg.norm_eps)
        x = x + attn_out
        h2 = rms_norm(x, params_l["ln2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            if cfg.family == "moe":
                mlp_out, _ = _mlp_or_moe(cfg, params_l, h2[:, None, :])
                mlp_out = mlp_out[:, 0]
            else:
                mlp_out, _ = _mlp_or_moe(cfg, params_l, h2)
        if cfg.alt_local_global:
            mlp_out = rms_norm(mlp_out, params_l["ln2_post"], cfg.norm_eps)
        return ((x + mlp_out).astype(in_dtype), conv, ssm), (k_l, v_l)

    layers = params["layers"]
    idx = jnp.arange(L)

    if cfg.family == "vlm":
        kk_ = cfg.cross_attn_every
        Lc = L // kk_
        self_stacked = jax.tree.map(lambda a: a.reshape((Lc, kk_) + a.shape[1:]), layers)
        win_s = windows.reshape(Lc, kk_)
        kc = cache.k.reshape((Lc, kk_) + cache.k.shape[1:])
        vc = cache.v.reshape((Lc, kk_) + cache.v.shape[1:])
        cross = params["cross_layers"]

        def vlm_step(x, xs):
            self_p, wins, k_b, v_b, idx_b, cross_p = xs
            (x, _, _), (k_new, v_new) = lax.scan(
                layer_step, (x, _EMPTY, _EMPTY), (self_p, wins, k_b, v_b, idx_b))
            h = rms_norm(x, cross_p["ln"], cfg.norm_eps)
            with jax.named_scope("attn"):
                gate = jnp.tanh(cross_p["gate"]).astype(x.dtype)
                x = (x + gate * _cross_attn(cfg, cross_p, h[:, None], media_emb)[:, 0]
                     ).astype(h.dtype)
            return x, (k_new, v_new)

        with jax.named_scope("layers"):
            x, (k_all, v_all) = lax.scan(
                vlm_step, x, (self_stacked, win_s, kc, vc, idx.reshape(Lc, kk_), cross)
            )
        new_cache = cache._replace(
            k=k_all.reshape(cache.k.shape), v=v_all.reshape(cache.v.shape), pos=pos + 1
        )
    else:
        with jax.named_scope("layers"):
            (x, conv, ssm), (k_all, v_all) = lax.scan(
                layer_step, (x, cache.conv, cache.ssm), (layers, windows, cache.k, cache.v, idx)
            )
        new_cache = cache._replace(k=k_all, v=v_all, conv=conv, ssm=ssm, pos=pos + 1)

    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = softcap((x @ head.astype(x.dtype)).astype(jnp.float32), cfg.logit_softcap)
    return logits, new_cache
