"""The plain reference model: embedding, the stack of one family's layers,
final norm, output head; cross-entropy, its gradient in blocks of rows, and
AdamW.  All float32.  Weights are the benchmark's own (``bench.weights``),
as a flat dict of stacked arrays.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax import lax

from bench.ref.common import F32, Numerics, mm, rms_norm


def _family(c):
    return importlib.import_module(f"bench.ref.{c['family']}")


def _layers(params):
    return {n[len("layers."):]: a for n, a in params.items() if n.startswith("layers.")}


def forward(c, params, tokens, num: Numerics = F32, start: int = 0):
    """Logits [B, S - start, V] (float32) at positions ``start..S-1`` of
    ``tokens`` [B, S].  A family module with ``layers(c, params, x, num)``
    runs its own stack (it gets every parameter); else one layer,
    ``layer(c, w, x, num)``, is scanned over the ``layers.`` stacks."""
    fam = _family(c)

    def body(x, w):
        return jax.checkpoint(lambda x, w: fam.layer(c, w, x, num))(x, w), None

    x = params["embed"][tokens]
    if hasattr(fam, "layers"):
        x = fam.layers(c, params, x, num)
    else:
        x, _ = lax.scan(body, x, _layers(params))
    x = rms_norm(x[:, start:], params["final_norm"], c["norm_eps"])
    head = params["embed"].T if c["tie_embeddings"] else params["lm_head"]
    return mm(num, x, head)


def ce_sum(c, params, tokens, num: Numerics = F32):
    """Summed next-token cross-entropy over rows of ``tokens`` [B, S]."""
    logits = forward(c, params, tokens, num)[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.lru_cache(maxsize=None)
def _grad_fn(c_items, num):
    c = dict(c_items)
    return jax.jit(jax.value_and_grad(lambda p, t: ce_sum(c, p, t, num)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(g, s):
    return jax.tree.map(lambda a: a * s, g)


def loss_and_grads(c, params, tokens, num: Numerics = F32, rows: int = 1):
    """Mean cross-entropy over ``tokens`` [B, S] and its gradient, computed
    ``rows`` rows at a time and summed into one buffer (so the device holds
    at most two gradients besides the parameters)."""
    f = _grad_fn(tuple(sorted(c.items())), num)
    B, S = tokens.shape
    total, grads = 0.0, None
    for r in range(0, B, rows):
        l, g = f(params, tokens[r:r + rows])
        total = total + l
        grads = g if grads is None else _add(grads, g)
        del g
    n = B * (S - 1)
    return total / n, _scale(grads, jnp.float32(1.0 / n))


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "weight_decay"),
                   donate_argnums=(0, 1, 2))
def adamw(params, m, v, grads, step, *, lr, b1, b2, eps, weight_decay):
    """One AdamW step in float32 (``step`` counts from 1)."""
    c1 = 1.0 - b1 ** step
    c2 = 1.0 - b2 ** step
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p),
        params, m, v)
    return params, m, v


def norms(tree) -> dict:
    return {n: float(jnp.sqrt(jnp.sum(jnp.square(a)))) for n, a in tree.items()}
