"""Seeded random weights for a configuration, made by the benchmark.

Each parameter is named as the benchmark names it (``layers.attn.wq``) and
drawn from its own key: ``fold_in(seed key, crc32(name))``, and each slice
of a stacked parameter from ``fold_in(that, index)``.  So one jitted call
makes the whole stack on the device.  Which parameters a configuration has,
their shapes and distributions, is its family's (``bench/families/``).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


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
    """name -> (shape, init, stacked) for every parameter of config ``c``,
    as its family (``bench/families/<family>.py``) lists them.

    ``init`` is ("normal", scale) | ("zeros",) | ("ones",) | ("full", value)
    | ("a_log",); ``stacked`` parameters have a leading stack axis.
    """
    return families.load(c).spec(c)


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


def make(c, key, names=None) -> dict:
    """Weights as a flat dict of f32 arrays (only ``names``, where given).
    Traceable: call it inside ``jax.jit``."""
    out = {}
    for name, (shape, init, stacked) in spec(c).items():
        if names is not None and name not in names:
            continue
        k = _leaf_key(key, name)
        if stacked:
            ks = jax.vmap(lambda l: jax.random.fold_in(k, l))(jnp.arange(shape[0]))
            out[name] = jax.vmap(lambda kk: _draw(init, kk, shape[1:]))(ks)
        else:
            out[name] = _draw(init, k, shape)
    return out


def n_params(c) -> int:
    return int(sum(np.prod(shape) for shape, _, _ in spec(c).values()))
