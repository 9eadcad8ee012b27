"""Multi-device train / prefill / serve steps.

``make_train_step`` builds one step function that runs identically on a
single device, a 2D data x model mesh (pure GSPMD: jit + in_shardings +
activation constraints), and a 3D pod x data x model mesh.  On a multi-pod
mesh the fwd/bwd runs in a **fully-manual** shard_map over every mesh axis
(hierarchical DP): gradients reduce in f32 over the cheap intra-pod "data"
links, then through the takum-compressed ring over the expensive inter-pod
links (``QuantPolicy.grad_comm`` picks the wire format, with stochastic
rounding per policy).  Fully manual because this XLA build rejects
ppermute/all_gather/axis_index inside partially-auto regions — so TP does
NOT compose with pod compression yet: params replicate across the manual
region and a nontrivial "model" axis merely duplicates compute (see
DESIGN.md §7 and the ROADMAP open item).

Spec builders (``train_state_specs`` / ``param_specs`` / ...) derive their
pytree structure from ``jax.eval_shape`` over the same constructors the
callers use, so the spec trees always match the real state trees leaf for
leaf (QTensor moments included).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.core.formats import wire_format
from repro.models import transformer as T
from repro.optim import adamw_init, adamw_update
from repro.quant.policy import is_takum
from repro.quant.qtensor import QTensor, dequantize, quantize

from . import actx
from . import faults
from . import sharding as shd
from .collectives import compressed_pmean, degraded_pmean

IS_STUB = False

P = jax.sharding.PartitionSpec


class TrainState(NamedTuple):
    params: Any
    opt: Any  # AdamWState
    rng: Any


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _has_pod(mesh) -> bool:
    return "pod" in mesh.axis_names and mesh.shape["pod"] > 1


def make_train_step(cfg, mesh, *, lr=3e-4, aux_weight: float = 0.01,
                    master_dtype=jnp.float32):
    """Build ``step(state, batch) -> (state, metrics)`` for ``cfg`` on ``mesh``.

    Metrics: ``loss`` (ce + aux), ``ce``, ``aux``, ``grad_norm`` (global L2
    norm of the reduced gradients) — all scalars.  On meshes
    with a nontrivial "pod" axis the gradient mean over pods runs through
    ``compressed_pmean`` in ``cfg.quant.grad_comm`` format; everything else
    (data-parallel reduction, TP psums) is GSPMD under jit.
    """
    del master_dtype  # the step is dtype-generic; accepted for API symmetry
    pod = _has_pod(mesh)

    def _loss(params, batch):
        return T.loss_fn(cfg, params, batch, aux_weight=aux_weight)

    if pod:
        fmt = cfg.quant.grad_comm
        guard = cfg.quant.guard
        # SR now covers OFP8 too (truncate-plus-dither, DESIGN.md §6);
        # bf16 and the block-scaled containers stay RNE
        wire_sr = cfg.quant.stochastic_rounding and wire_format(fmt).supports_sr

        def fwd_bwd_local(batch_axes):
            def f(params, batch, wire_key):
                (loss, metrics), grads = jax.value_and_grad(_loss, has_aux=True)(
                    params, batch
                )
                # chaos hook: identity unless a faults.inject scope was
                # active at trace time (grad_poison_rate > 0)
                grads = faults.poison_grads(grads, wire_key)
                data_axes = tuple(a for a in batch_axes if a != "pod")
                if wire_sr:
                    # decorrelate SR noise across pods; data/model replicas
                    # of one pod share the key so their rings stay bitwise
                    # identical (see collectives.compressed_psum)
                    wire_key = jax.random.fold_in(
                        wire_key, jax.lax.axis_index("pod")
                    )

                # raw-gradient health, checked BEFORE any containment zeroes
                # the evidence: pmean'd into the [0,1] fraction of devices
                # whose local grads were all-finite (1.0 = clean step)
                flat, treedef = jax.tree.flatten(grads)
                grads_ok = jnp.float32(1)
                for g in flat:
                    grads_ok = grads_ok * jnp.isfinite(g).all().astype(jnp.float32)
                if data_axes:
                    flat = jax.lax.pmean(flat, data_axes)

                # one data-axis pmean and one compressed ring per leaf.  A
                # single flat payload (all leaves concatenated into one 1-D
                # array) gives the same numerics, but the TPU compiler takes
                # minutes over the concatenate/split at model size and
                # overflows its stack at 8 hymba_1_5b layers.  Each leaf's
                # SR key is its own, so the noise is independent across
                # leaves as it was across the flat payload.
                def reduce(i, g):
                    key = jax.random.fold_in(wire_key, i) if wire_sr else None
                    g32 = g.astype(jnp.float32)
                    if guard is None:
                        out = compressed_pmean(g32, "pod", fmt, sr_key=key)
                    else:
                        out = degraded_pmean(g32, "pod", fmt, guard, sr_key=key)
                    return out.astype(g.dtype)

                grads = jax.tree.unflatten(
                    treedef, [reduce(i, g) for i, g in enumerate(flat)]
                )
                loss = jax.lax.pmean(loss, batch_axes)
                metrics = {**metrics, "grad_ok": grads_ok}
                metrics = jax.tree.map(
                    lambda m: jax.lax.pmean(m, batch_axes), metrics
                )
                return loss, metrics, grads

            return f

        def fwd_bwd(params, batch, wire_key):
            # built at trace time: the usable batch axes depend on the
            # (now known) global batch size
            B = batch["tokens"].shape[0]
            axes = shd.batch_dim_axes(mesh, B)
            if "pod" not in axes:
                raise ValueError(
                    f"global batch {B} must divide by the pod axis "
                    f"({mesh.shape['pod']}) for compressed pod reduction"
                )
            return jax.shard_map(
                fwd_bwd_local(axes), mesh=mesh,
                in_specs=(P(), P(axes), P()), out_specs=(P(), P(), P()),
                check_vma=False,
            )(params, batch, wire_key)
    else:

        def fwd_bwd(params, batch, wire_key):
            # single-pod: GSPMD reduces grads in f32; wire_key only feeds
            # the (trace-time-gated) chaos hook
            def loss_in_ctx(params, batch):
                with actx.use_mesh(mesh):
                    return _loss(params, batch)

            (loss, metrics), grads = jax.value_and_grad(loss_in_ctx, has_aux=True)(
                params, batch
            )
            grads = faults.poison_grads(grads, wire_key)
            ok = jnp.float32(1)
            for g in jax.tree.leaves(grads):
                ok = ok * jnp.isfinite(g).all().astype(jnp.float32)
            metrics = {**metrics, "grad_ok": ok}
            return loss, metrics, grads

    def step(state: TrainState, batch):
        with telemetry.trace_span("step.train", cat="step") as sp:
            rng, sr_key, wire_key = jax.random.split(state.rng, 3)
            loss, metrics, grads = fwd_bwd(state.params, batch, wire_key)
            # global L2 norm of the reduced gradients (after the compressed
            # ring on pod meshes)
            gn = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)
            ))
            if telemetry.enabled():
                # one record per *execution* of this trace (the step runs
                # outside shard_map, so multiplicity is 1, not n_devices)
                telemetry.emit("step.calls", jnp.float32(1))
                tok = batch.get("tokens")
                if tok is not None:
                    telemetry.emit("step.tokens", float(tok.shape[0] * tok.shape[1]))
                telemetry.emit_hist("step.grad_norm", gn)
            use_sr = cfg.quant.stochastic_rounding and is_takum(cfg.quant.opt_state)
            new_params, new_opt = adamw_update(
                grads, state.opt, state.params, lr=lr, fmt=cfg.quant.opt_state,
                key=sr_key if use_sr else None,
            )
            out = {"loss": loss, "ce": metrics["ce"], "aux": metrics["aux"],
                   "grad_norm": gn}
            guard = cfg.quant.guard
            if guard is not None and guard.skip_nonfinite_update:
                # GradScaler-style microbatch skip: a step whose raw gradients
                # were not everywhere finite leaves params AND opt state
                # untouched (training on contained-to-zero garbage would still
                # corrupt the Adam moments).  grad_ok is a pmean'd fraction, so
                # every device takes the same branch.
                ok = metrics["grad_ok"] >= jnp.float32(0.999)
                keep = lambda n, o: jnp.where(ok, n, o)
                params = jax.tree.map(keep, new_params, state.params)
                opt = jax.tree.map(keep, new_opt, state.opt)
                telemetry.emit("step.skipped", jnp.float32(1) - ok.astype(jnp.float32))
                out["grad_ok"] = metrics["grad_ok"]
            else:
                params, opt = new_params, new_opt
            sp.dep = telemetry.probe(loss)
        return TrainState(params=params, opt=opt, rng=rng), out

    return step


def train_step(state, batch, *, cfg, mesh, **kw):
    """One-off convenience: builds the step and applies it (untraced)."""
    return make_train_step(cfg, mesh, **kw)(state, batch)


# ---------------------------------------------------------------------------
# shapes and specs
# ---------------------------------------------------------------------------


def param_shapes(cfg, dtype=jnp.float32):
    """ShapeDtypeStruct tree of the raw (training) parameter pytree."""
    return jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    )


def state_shapes(cfg, *, master_dtype=jnp.float32):
    """ShapeDtypeStruct tree of the full TrainState (params + AdamW + rng)."""

    def mk():
        params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=master_dtype)
        return TrainState(
            params=params,
            opt=adamw_init(params, fmt=cfg.quant.opt_state),
            rng=jax.random.PRNGKey(1),
        )

    return jax.eval_shape(mk)


def train_state_specs(cfg, mesh, *, master_dtype=jnp.float32):
    """PartitionSpec tree matching :func:`state_shapes` on ``mesh``.

    Params follow the TP rule table; AdamW moments mirror their parameter's
    spec (QTensor bits by name+rank, scalar scales replicated); step counter
    and rng replicate.  No surface is sharded over "pod" — parameters are
    replicated across pods (plain multi-pod DP), which is also what the
    manual-pod compressed-gradient path requires.
    """
    shapes = state_shapes(cfg, master_dtype=master_dtype)
    rules = shd.rules_for(cfg, mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: shd.spec_for(path, leaf, rules, mesh), shapes
    )


def train_state_specs_nopod(cfg, mesh, *, master_dtype=jnp.float32):
    """Alias of :func:`train_state_specs` guaranteed pod-free (the rule table
    never uses "pod"; this name documents the invariant at call sites)."""
    return train_state_specs(cfg, mesh, master_dtype=master_dtype)


# ---------------------------------------------------------------------------
# serving: quantised weights, prefill + decode
# ---------------------------------------------------------------------------


def quantize_params(cfg, params):
    """Pack weights into ``cfg.quant.weights`` storage (takum/OFP8 -> QTensor
    with per-tensor power-of-two scale; norm gains and other 1D leaves stay
    f32; IEEE formats are a plain dtype cast)."""
    fmt = cfg.quant.weights
    wf = wire_format(fmt)
    if wf.family == "ieee":
        dt = jnp.bfloat16 if wf.name == "bf16" else jnp.float32
        return jax.tree.map(lambda a: a.astype(dt), params)

    def q(a):
        if a.ndim >= 2:
            return quantize(a.astype(jnp.float32), wf.name, scaled=True)
        return a.astype(jnp.float32)

    return jax.tree.map(q, params)


def dequantize_params(params):
    """Inverse of :func:`quantize_params` (QTensor -> f32, rest unchanged)."""
    return jax.tree.map(
        lambda a: dequantize(a) if isinstance(a, QTensor) else a,
        params, is_leaf=lambda a: isinstance(a, QTensor),
    )


def serve_param_shapes(cfg):
    """ShapeDtypeStruct tree of the quantised serving parameter pytree."""
    return jax.eval_shape(
        lambda: quantize_params(
            cfg, T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        )
    )


def make_prefill_step(cfg, mesh, *, cache_len: int | None = None):
    """``step(params, batch) -> (last_logits, cache)`` (quantised weights).

    ``cache_len`` is the prompt length plus the decode budget: the returned
    cache has that many positions, so each :func:`make_serve_step` call
    after it writes a free slot.  Left at None the cache is exactly the
    prompt long and holds no room for decoding.
    """

    def step(params, batch):
        p = dequantize_params(params)
        with actx.use_mesh(mesh):
            return T.prefill(
                cfg, p, batch["tokens"], batch.get("media"), cache_len=cache_len
            )

    return step


def make_serve_step(cfg, mesh):
    """``step(params, batch, cache) -> (logits, cache)`` single-token decode."""

    def step(params, batch, cache):
        p = dequantize_params(params)
        with actx.use_mesh(mesh):
            return T.decode_step(cfg, p, batch["token"], cache, batch.get("media"))

    return step
