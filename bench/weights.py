"""Seeded random weights for a configuration, made by the benchmark.

Each parameter is named as the benchmark names it (``layers.attn.wq``) and
drawn from its own key: ``fold_in(seed key, crc32(name))``, and for a
per-layer parameter ``fold_in(that, layer)``.  So one jitted call makes the
whole stack on the device, and the reference can make layer ``l`` alone and
get the same numbers.  The distributions follow the repository's model
family conventions (normal matrices scaled by ``fan_in ** -0.5``, zero norm
gains, Mamba-2's ``A``, ``dt`` bias and ``D``).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import ssm_dims


def seed_key(seed: int, stream: str):
    """A key for one stream ("weights", "data", ...) of a run's seed.
    ``seed`` may exceed 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(stream.encode()) & 0x7FFFFFFF)


def spec(c) -> dict:
    """name -> (shape, init, stacked) for every parameter of config ``c``.

    ``init`` is ("normal", scale) | ("zeros",) | ("ones",) | ("full", value)
    | ("a_log",); ``stacked`` parameters have a leading layer axis.
    """
    d, L, V = c["d_model"], c["num_layers"], c["vocab_size"]
    s = {"embed": ((V, d), ("normal", d ** -0.5), False)}
    s["layers.ln1"] = ((L, d), ("zeros",), True)
    if c["family"] != "ssm":
        H, Kv, hd, f = c["num_heads"], c["num_kv_heads"], c["head_dim"], c["d_ff"]
        s["layers.ln2"] = ((L, d), ("zeros",), True)
        s["layers.attn.wq"] = ((L, d, H * hd), ("normal", d ** -0.5), True)
        s["layers.attn.wk"] = ((L, d, Kv * hd), ("normal", d ** -0.5), True)
        s["layers.attn.wv"] = ((L, d, Kv * hd), ("normal", d ** -0.5), True)
        s["layers.attn.wo"] = ((L, H * hd, d), ("normal", (H * hd) ** -0.5), True)
        s["layers.mlp.wi"] = ((L, d, f), ("normal", d ** -0.5), True)
        s["layers.mlp.wg"] = ((L, d, f), ("normal", d ** -0.5), True)
        s["layers.mlp.wo"] = ((L, f, d), ("normal", f ** -0.5), True)
    if c["family"] in ("ssm", "hybrid"):
        m = ssm_dims(c)
        din, nh, N, w, F = m["d_in"], m["nh"], m["N"], m["w"], m["F"]
        s["layers.ssm.in_proj"] = ((L, d, 2 * din + 2 * N + nh), ("normal", d ** -0.5), True)
        s["layers.ssm.conv_w"] = ((L, w, F), ("normal", 0.2), True)
        s["layers.ssm.conv_b"] = ((L, F), ("zeros",), True)
        s["layers.ssm.a_log"] = ((L, nh), ("a_log",), True)
        s["layers.ssm.dt_bias"] = ((L, nh), ("full", -4.6), True)
        s["layers.ssm.D"] = ((L, nh), ("ones",), True)
        s["layers.ssm.norm_g"] = ((L, din), ("zeros",), True)
        s["layers.ssm.out_proj"] = ((L, din, d), ("normal", din ** -0.5), True)
    s["final_norm"] = ((d,), ("zeros",), False)
    if not c["tie_embeddings"]:
        s["lm_head"] = ((d, V), ("normal", d ** -0.5), False)
    return s


def _draw(init, key, shape):
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * init[1]
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "full":
        return jnp.full(shape, init[1], jnp.float32)
    if kind == "a_log":
        return jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32))
    raise ValueError(init)


def _leaf_key(key, name):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def make(c, key, layer: int | None = None, names=None) -> dict:
    """Weights as a flat dict of f32 arrays.  With ``layer`` given, only the
    per-layer parameters, each without its layer axis.  Traceable: call it
    inside ``jax.jit``."""
    out = {}
    for name, (shape, init, stacked) in spec(c).items():
        if names is not None and name not in names:
            continue
        k = _leaf_key(key, name)
        if layer is not None:
            if stacked:
                out[name] = _draw(init, jax.random.fold_in(k, layer), shape[1:])
            continue
        if stacked:
            ks = jax.vmap(lambda l: jax.random.fold_in(k, l))(jnp.arange(shape[0]))
            out[name] = jax.vmap(lambda kk: _draw(init, kk, shape[1:]))(ks)
        else:
            out[name] = _draw(init, k, shape)
    return out


def n_params(c) -> int:
    return int(sum(np.prod(shape) for shape, _, _ in spec(c).values()))
