"""Training launcher: end-to-end driver over the framework stack.

    PYTHONPATH=src python -m repro.launch.train --arch llama3_8b --smoke \
        --steps 200 --batch 8 --seq 256 --policy takum

Uses the real substrate: synthetic-Markov data pipeline, AdamW (optionally
takum-quantised moments), checkpoint/restart, metrics CSV.  ``--mesh``
runs the same step function on a data x model or pod x data x model mesh;
the default ``1x1`` is one device.  :func:`setup` builds the step and
:func:`run` the loop around it; ``chip_smoke.py`` calls both.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax

from repro import configs
from repro.data import SyntheticLM
from repro.dist import sharding as shd
from repro.dist import step as dstep
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import parse_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adamw_init
from repro.quant.policy import POLICIES
from repro.train import CheckpointManager, TrainLoop, TrainLoopConfig


def lm_100m() -> ModelConfig:
    """~100M-parameter llama-style config for the end-to-end example."""
    return ModelConfig(
        name="lm-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32768,
        head_dim=64, rope_theta=10000.0, tie_embeddings=True,
    )


def build(arch: str, *, smoke: bool, policy: str, seq: int, batch: int):
    if arch == "lm_100m":
        cfg = lm_100m()
    else:
        cfg = configs.get_smoke(arch) if smoke else configs.get(arch)
    cfg = cfg.with_(quant=POLICIES[policy])
    pipe = SyntheticLM(cfg.vocab_size, seq, batch, seed=17)
    return cfg, pipe


def setup(cfg, pipe, *, lr: float = 3e-4, mesh: str = "1x1"):
    """The pieces of a training run of ``cfg`` on ``pipe`` through
    ``dist.step``: ``(step_fn, batch_fn, init_state, state_sharding)``.

    ``mesh`` is a :func:`~repro.launch.mesh.parse_mesh` spec.  ``step_fn``
    is the jitted step (train state donated), ``batch_fn(s)`` the placed
    batch of step ``s``, ``init_state()`` the initial state, placed with
    ``state_sharding`` (the NamedSharding tree of the state, None on a
    single device).
    """
    m = parse_mesh(mesh)
    base_step = dstep.make_train_step(cfg, m, lr=lr)
    sharded = any(v > 1 for v in m.shape.values())

    def make_batch(s):
        b = pipe.batch(s)
        if cfg.family == "vlm":
            b["media"] = pipe.media_stub(s, cfg.num_media_tokens, cfg.media_d)
        return b

    if sharded:
        sspec = shd.named(m, dstep.train_state_specs(cfg, m))
        bspec = shd.named(
            m, shd.batch_specs(cfg, m, kind="train", batch=pipe.global_batch)
        )
        step_fn = jax.jit(base_step, in_shardings=(sspec, bspec),
                          out_shardings=(sspec, None), donate_argnums=(0,))
        batch_fn = lambda s: jax.device_put(make_batch(s), bspec)
        print(f"mesh={dict(m.shape)} (dist.step routing)")
    else:
        sspec = None
        step_fn = jax.jit(base_step, donate_argnums=(0,))
        batch_fn = make_batch

    # one compiled program, placed where the step wants it: op-by-op, the
    # init of an 8-layer hymba_1_5b took 53 s on a TPU v5e
    @functools.partial(jax.jit, out_shardings=sspec)
    def init_state():
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params, fmt=cfg.quant.opt_state)
        return dstep.TrainState(params=params, opt=opt, rng=jax.random.PRNGKey(1))

    return step_fn, batch_fn, init_state, sspec


def run(cfg, pipe, *, steps: int, lr: float = 3e-4, mesh: str = "1x1",
        ckpt_dir: str, ckpt_every: int, log_every: int = 10):
    """Train ``cfg`` on ``pipe`` for ``steps`` steps (see :func:`setup`).

    Checkpoints go to ``ckpt_dir`` every ``ckpt_every`` steps and at the
    last step, in ``cfg.quant.checkpoint`` format; a checkpoint already in
    ``ckpt_dir`` is resumed from.  Returns ``(loop, state)``: the finished
    :class:`~repro.train.TrainLoop` (its ``metrics_history`` holds one entry
    every ``log_every`` steps) and the final train state.
    """
    step_fn, batch_fn, init_state, sspec = setup(cfg, pipe, lr=lr, mesh=mesh)
    loop = TrainLoop(
        TrainLoopConfig(
            total_steps=steps, ckpt_every=ckpt_every,
            ckpt_dir=ckpt_dir, ckpt_fmt=cfg.quant.checkpoint,
            log_every=log_every,
        ),
        step_fn,
        batch_fn,
        init_state,
        state_sharding=sspec,
    )
    state = loop.run()
    return loop, state


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="lm_100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="takum", choices=list(POLICIES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--metrics-out", default="")
    ap.add_argument("--mesh", default="1x1",
                    help="device mesh, e.g. 2x4 (data x model) or 2x2x2 "
                         "(pod x data x model); pod meshes use the "
                         "takum-compressed gradient ring")
    args = ap.parse_args()

    cfg, pipe = build(args.arch, smoke=args.smoke, policy=args.policy,
                      seq=args.seq, batch=args.batch)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M policy={args.policy}")

    t0 = time.time()
    loop, _ = run(cfg, pipe, steps=args.steps, lr=args.lr, mesh=args.mesh,
                  ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    dt = time.time() - t0
    hist = loop.metrics_history
    print(f"done {args.steps} steps in {dt:.1f}s")
    for m in hist[:3] + hist[-3:]:
        print("  ", {k: round(v, 4) for k, v in m.items()})
    if hist:
        first, last = hist[0]["ce"], hist[-1]["ce"]
        print(f"CE {first:.3f} -> {last:.3f} ({'improved' if last < first else 'NO IMPROVEMENT'})")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()
