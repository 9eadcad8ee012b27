"""One decoder layer of the ``hybrid`` family (Hymba as this repository
defines it): a sliding-window GQA attention branch and a Mamba-2 branch read
the same normed input in parallel and are averaged, then a SwiGLU MLP.

Departures from the Hymba paper, which the program shares: every layer is
windowed (the paper keeps three global layers), there are no meta-tokens,
no cross-layer KV sharing, and the SSM inner width is ``d_model``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.ref.common import mm, rms_norm, rope, ssd_mixer, window_attention


def layer(c, w, x, num):
    """x [B, S, d] -> [B, S, d]; ``w`` maps names below ``layers.`` to one
    layer's float32 weights."""
    B, S, _ = x.shape
    H, Kv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    eps = c["norm_eps"]
    pos = jnp.arange(S)
    h = rms_norm(x, w["ln1"], eps)
    q = rope(mm(num, h, w["attn.wq"]).reshape(B, S, H, hd), pos, c["rope_theta"])
    k = rope(mm(num, h, w["attn.wk"]).reshape(B, S, Kv, hd), pos, c["rope_theta"])
    v = mm(num, h, w["attn.wv"]).reshape(B, S, Kv, hd)
    att = window_attention(q, num.kv(k), num.kv(v), c["sliding_window"])
    att = mm(num, att, w["attn.wo"])
    ssm = ssd_mixer(c, {n[4:]: a for n, a in w.items() if n.startswith("ssm.")}, h, num)
    x = x + 0.5 * (att + ssm)
    h2 = rms_norm(x, w["ln2"], eps)
    return x + mm(num, jax.nn.silu(mm(num, h2, w["mlp.wg"])) * mm(num, h2, w["mlp.wi"]),
                  w["mlp.wo"])
