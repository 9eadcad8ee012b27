"""How ``correct`` is decided: the plain reference run after the window,
and the numbers compared with it.

Training (three steps from the same weights and batches):

* ``loss_gap``: the largest relative gap of a step's cross-entropy;
* ``grad_norm_gap``: per parameter, the gap between the norm of the first
  gradient as the program's optimizer got it (its first Adam moment after
  one step, over ``1 - b1``) and the reference's, over the larger of the
  reference's norm of that parameter and of the median parameter; the
  largest over parameters;
* ``change_gap``: the same for the norm of each parameter's change over the
  three steps.  Parameters whose reference gradient is under a thousandth
  of the median parameter's move by round-off alone under Adam and are
  left out of it.

Serving: ``logit_gap``, the widest gap by which the logit of a token the
program served lies below the reference's best logit at that position,
over the sampled sessions' prompts and served tokens.  The control reads
the same gap for the token that the reference computed one precision
step lower puts first.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.ref import model as ref
from bench.ref.common import F32, Numerics


def _gap(p: float, r: float, floor: float) -> float:
    return abs(p - r) / max(abs(r), floor)


def train_numbers(prog: dict, want: dict) -> tuple[dict, list]:
    """The three training numbers of ``prog`` against ``want`` (each with
    ``loss`` [per step], ``grad_norm`` and ``change`` {param: norm}), and
    the parameters left out of ``change_gap``."""
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], want["loss"], strict=True))
    g = want["grad_norm"]
    med_g = statistics.median(g.values())
    grad_gap = max(_gap(prog["grad_norm"][n], g[n], med_g) for n in g)
    keep = [n for n in g if g[n] >= 1e-3 * med_g]
    ch = want["change"]
    med_c = statistics.median(ch[n] for n in keep)
    change_gap = max(_gap(prog["change"][n], ch[n], med_c) for n in keep)
    nums = {"loss_gap": loss_gap, "grad_norm_gap": grad_gap, "change_gap": change_gap}
    return {k: float(v) for k, v in nums.items()}, sorted(set(g) - set(keep))


def train_reference(c: dict, opt: dict, wkey, batch_fn, steps: int, rows: int,
                    num: Numerics = F32, half: bool = False) -> dict:
    """Train the reference ``steps`` steps from the benchmark's weights on
    the same batches.  ``half`` trains on the first half of each batch
    only (a fault the comparison must catch).

    The Adam moments wait on the host while the gradient is computed, and
    the change is taken against the weights made again from the seed, so
    the device holds at most the parameters and two gradients, or the
    parameters, one gradient and the moments."""
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: weights.make(c, k))(wkey)
        m = v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), params)
        out = {"loss": []}
        hp = {k: opt[k] for k in ("lr", "b1", "b2", "eps", "weight_decay")}
        for s in range(steps):
            toks = batch_fn(s)["tokens"]
            if half:
                toks = toks[: toks.shape[0] // 2]
            loss, g = ref.loss_and_grads(c, params, toks, num, rows=rows)
            out["loss"].append(float(loss))
            if s == 0:
                out["grad_norm"] = ref.norms(g)
            m, v = jax.device_put((m, v))
            params, m, v = ref.adamw(params, m, v, g, jnp.float32(s + 1), **hp)
            del g
            m, v = jax.device_get((m, v))
        del m, v
        out["change"] = {n: float(x) for n, x in jax.jit(lambda p, k: {
            n: jnp.sqrt(jnp.sum(jnp.square(a - weights.make(c, k, names={n})[n])))
            for n, a in p.items()})(params, wkey).items()}
    return out


def _pad_to(n: int, q: int = 256) -> int:
    return -(-n // q) * q


def decode_gaps(c: dict, wkey, seqs: np.ndarray, prompt_len: int,
                control: bool = False) -> dict:
    """``seqs`` [n, prompt_len + served]: each a prompt and the tokens the
    program served after it.  Returns the widest ``logit_gap`` and, with
    ``control``, the control's under ``control``; each gap is taken per
    position."""
    from bench.ref.common import CONTROL

    n, T = seqs.shape
    served = T - prompt_len
    Tp = prompt_len + _pad_to(served)  # a few shapes only, so they cache
    toks = jnp.asarray(np.pad(seqs, ((0, 0), (0, Tp - T))), jnp.int32)
    out = {}
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: weights.make(c, k))(wkey)
        fwd = jax.jit(lambda p, t, num: ref.forward(c, p, t, num, start=prompt_len - 1),
                      static_argnums=(2,))
        logits = fwd(params, toks, F32)[:, :served]  # predicts positions P .. T-1
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, toks[:, prompt_len:prompt_len + served, None], -1)[..., 0]
        out["logit_gap"] = float(jnp.max(best - got))
        if control:
            cl = fwd(params, toks, CONTROL)[:, :served]
            pick = jnp.argmax(cl, axis=-1)
            cg = best - jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
            out["control"] = {"logit_gap": float(jnp.max(cg))}
    return out
