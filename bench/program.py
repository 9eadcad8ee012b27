"""The program under test, as the benchmark drives it.

Everything the benchmark takes from the repository goes through this
module: the model configuration and wire policy, the parameter tree, the
train step (``repro.launch.train.setup``) and the serving steps
(``repro.dist.step.make_prefill_step`` / ``make_serve_step``).
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

from repro.dist import step as dstep  # noqa: E402
from repro.launch import train as launch_train  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402,F401
from repro.launch.mesh import parse_mesh  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.mamba2 import MambaParams  # noqa: E402
from repro.optim import adamw_init  # noqa: E402
from repro.optim.adamw import adamw_update  # noqa: E402
from repro.quant.policy import POLICIES  # noqa: E402
from repro.quant.qtensor import QTensor, dequantize  # noqa: E402

MODEL_FIELDS = tuple(f.name for f in dataclasses.fields(ModelConfig)
                     if f.name not in ("name", "quant"))


def model_config(cfg: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a configuration file.  Fails where
    the program's named policy stores a surface in another format than the
    file declares."""
    wire = dict(cfg["wire"])
    policy = POLICIES[wire.pop("policy")]
    for surface, fmt in wire.items():
        have = getattr(policy, surface)
        if have != fmt:
            raise ValueError(f"{cfg['name']}: the program's policy stores {surface} "
                             f"as {have!r}, the configuration declares {fmt!r}")
    fields = {k: cfg[k] for k in MODEL_FIELDS if k in cfg}
    return ModelConfig(name=cfg["name"], quant=policy, **fields)


def check_optimizer(opt: dict) -> None:
    """The program's AdamW hyperparameters must be the ones declared."""
    import inspect

    defaults = {k: p.default for k, p in inspect.signature(adamw_update).parameters.items()}
    for k in ("b1", "b2", "eps", "weight_decay"):
        if defaults[k] != opt[k]:
            raise ValueError(f"the program's AdamW has {k}={defaults[k]}, "
                             f"the configuration declares {opt[k]}")


def to_tree(flat: dict) -> dict:
    """The benchmark's flat weights -> the program's parameter tree."""
    tree: dict = {"layers": {}}
    for name, a in flat.items():
        parts = name.split(".")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = a
    ssm = tree["layers"].get("ssm")
    if ssm is not None:
        tree["layers"]["ssm"] = MambaParams(**ssm)
    return tree


def from_tree(tree) -> dict:
    """The program's parameter tree (or a tree of the same structure) -> flat."""
    out = {}

    def walk(prefix, node):
        if isinstance(node, MambaParams):
            node = node._asdict()
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}{k}.", v)
        else:
            out[prefix[:-1]] = node

    walk("", tree)
    return out


def check_layout(cfgm: ModelConfig, flat_shapes: dict) -> None:
    """The benchmark's weights must have the program's tree and shapes."""
    want = dstep.param_shapes(cfgm)
    got = to_tree(flat_shapes)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{jax.tree.structure(want)}\n{jax.tree.structure(got)}")
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if tuple(w.shape) != tuple(g.shape):
            raise ValueError(f"parameter shape {g.shape} differs from the program's {w.shape}")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


class _Pipe:
    """What ``launch.train.setup`` asks of a data pipeline."""

    def __init__(self, batch_fn, global_batch):
        self.batch = batch_fn
        self.global_batch = global_batch


def train_setup(cfgm, batch_fn, global_batch: int, *, lr: float, mesh: str):
    """(jitted step with the state donated, placed batch fn, state sharding)."""
    step_fn, place, _, sspec = launch_train.setup(
        cfgm, _Pipe(batch_fn, global_batch), lr=lr, mesh=mesh)
    return step_fn, place, sspec


def init_train_state(cfgm, make_params, wkey, rng, sspec):
    """One jitted call: the benchmark's weights ``make_params(wkey)`` as
    master params, zero AdamW moments in the policy's format, and the step's
    rng.  The keys are arguments, so every seed runs the same program."""

    def init(wkey, rng):
        params = to_tree(make_params(wkey))
        return dstep.TrainState(params=params, opt=adamw_init(params, fmt=cfgm.quant.opt_state),
                                rng=rng)

    return jax.jit(init, out_shardings=sspec)(wkey, rng)


def first_moment_norms(state) -> dict:
    """Per-parameter L2 norm of the first Adam moment, as the program
    stores it (dequantised)."""

    def f(m):
        m = jax.tree.map(lambda a: dequantize(a) if isinstance(a, QTensor) else a.astype(jnp.float32),
                         m, is_leaf=lambda a: isinstance(a, QTensor))
        return {n: jnp.sqrt(jnp.sum(jnp.square(a))) for n, a in from_tree(m).items()}

    return {n: float(v) for n, v in jax.jit(f)(state.opt.m).items()}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_weights(cfgm, make_params, wkey):
    """One jitted call: the benchmark's weights ``make_params(wkey)`` packed
    by the program's ``quantize_params`` into the policy's weight format."""
    return jax.jit(lambda k: dstep.quantize_params(cfgm, to_tree(make_params(k))))(wkey)


def prefill_step(cfgm, mesh: str, cache_len):
    return jax.jit(dstep.make_prefill_step(cfgm, parse_mesh(mesh), cache_len=cache_len))


def decode_step(cfgm, mesh: str):
    """The serve step (cache donated) with the greedy token taken on the
    device: ``(params, token [B], cache) -> (next token [B], cache)``."""
    serve = dstep.make_serve_step(cfgm, parse_mesh(mesh))

    def step(params, token, cache):
        logits, cache = serve(params, {"token": token}, cache)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    return jax.jit(step, donate_argnums=(2,))


def stack_caches(caches):
    """Per-group caches -> one cache, sessions along the batch axis."""

    def cat(*xs):
        return xs[0] if xs[0].ndim == 0 else jnp.concatenate(xs, axis=1)

    return jax.jit(lambda cs: jax.tree.map(cat, *cs))(caches)


def replay_fn(prompt_len: int):
    """``(cache, conv, ssm) -> cache`` at position ``prompt_len`` with the
    SSM and conv state of the prompt's end (copied, so the snapshot
    survives the donation of the cache)."""

    def replay(cache, conv, ssm):
        return cache._replace(pos=jnp.int32(prompt_len), conv=jnp.copy(conv), ssm=jnp.copy(ssm))

    return jax.jit(replay, donate_argnums=(0,))
