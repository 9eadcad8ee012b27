"""The program under test, as the benchmark drives it.

Everything the benchmark takes from the repository goes through this
module: the model configuration and wire policy, the parameter tree, the
train step (``repro.launch.train.setup``) and the serving steps
(``repro.dist.step.make_prefill_step`` / ``make_serve_step``).
"""

from __future__ import annotations

import dataclasses
import functools
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
from repro.optim import adamw_init  # noqa: E402
from repro.optim.adamw import adamw_update  # noqa: E402
from repro.quant.policy import POLICIES  # noqa: E402
from repro.quant.qtensor import QTensor, dequantize  # noqa: E402


def model_fields() -> tuple[str, ...]:
    """The program's model sizes: ``ModelConfig``'s fields but its name and
    wire policy."""
    return tuple(f.name for f in dataclasses.fields(ModelConfig)
                 if f.name not in ("name", "quant"))


def hashable(v):
    """A configuration value as a hashable one: a JSON list as a tuple."""
    return tuple(hashable(x) for x in v) if isinstance(v, list) else v


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
    fields = {k: hashable(cfg[k]) for k in model_fields() if k in cfg}
    return ModelConfig(name=cfg["name"], quant=policy, **fields)


def check_optimizer(opt: dict) -> None:
    """The program's AdamW hyperparameters must be the ones declared."""
    import inspect

    defaults = {k: p.default for k, p in inspect.signature(adamw_update).parameters.items()}
    for k in ("b1", "b2", "eps", "weight_decay"):
        if defaults[k] != opt[k]:
            raise ValueError(f"the program's AdamW has {k}={defaults[k]}, "
                             f"the configuration declares {opt[k]}")


def _path_name(path) -> str:
    """``layers.ssm.in_proj`` for a tree path of dict keys and tuple fields."""
    return ".".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


@functools.lru_cache(maxsize=None)
def _layout(cfgm: ModelConfig):
    """(tree structure, leaf names, leaf shapes) of the program's parameters."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(dstep.param_shapes(cfgm))
    return (treedef, tuple(_path_name(p) for p, _ in leaves),
            tuple(tuple(a.shape) for _, a in leaves))


def to_tree(cfgm: ModelConfig, flat: dict):
    """The benchmark's flat weights -> the program's parameter tree, in the
    structure of ``dstep.param_shapes``."""
    treedef, names, _ = _layout(cfgm)
    return jax.tree.unflatten(treedef, [flat[n] for n in names])


def from_tree(tree) -> dict:
    """The program's parameter tree (or a tree of the same structure) -> flat."""
    return {_path_name(p): a for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def check_layout(cfgm: ModelConfig, flat_shapes: dict) -> None:
    """The benchmark's weights must have the program's names and shapes."""
    _, names, shapes = _layout(cfgm)
    if set(names) != set(flat_shapes):
        raise ValueError(f"parameters differ from the program's: the benchmark alone has "
                         f"{sorted(set(flat_shapes) - set(names))}, the program alone "
                         f"{sorted(set(names) - set(flat_shapes))}")
    for n, want in zip(names, shapes):
        if tuple(flat_shapes[n].shape) != want:
            raise ValueError(f"parameter {n}: shape {tuple(flat_shapes[n].shape)} differs "
                             f"from the program's {want}")


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
        params = to_tree(cfgm, make_params(wkey))
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
    return jax.jit(lambda k: dstep.quantize_params(cfgm, to_tree(cfgm, make_params(k))))(wkey)


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
