#!/usr/bin/env python3
"""Run the main path once on one TPU, in one process, and check what comes out.

    python3 chip_smoke.py               # one chip: kernel, serve and train phases
    python3 chip_smoke.py --four-chips  # four chips: the sharded train step only

Phases on one chip, each through the entry points a user calls:

1. kernels: ``repro.kernels.ops.{encode,decode,matmul,decode_attention}``
   compiled natively (no interpreter) at ``hymba_1_5b`` widths for t8, t16,
   e4m3 and mxt8 under each format's default codec impl.  Each compiled
   program must contain a ``tpu_custom_call`` (the Pallas kernel, not a
   reference fallback), and each result is compared with ``kernels/ref.py``.
2. serve: ``hymba_1_5b`` at all 32 layers and published widths, random
   weights from a seed, ``takum`` policy: ``dist.step.quantize_params``,
   ``make_prefill_step`` on 8 prompts of 2048 tokens, then 16
   ``make_serve_step`` decode steps with the cache donated.  Logits and
   every KV-cache slot of two sequences are compared with an f32
   ``transformer.forward`` over the whole token sequence.
3. train: the launcher's loop (``repro.launch.train.run``) on ``hymba_1_5b``
   cut to 8 layers, ``takum`` policy, batch 8 x 2048 tokens, 6 steps on a
   1x1 mesh, saving one checkpoint that must restore with valid CRCs.

``--four-chips`` runs only one train step of the phase-3 model on a 2x2
(data x model) and a 2x2x1 (pod x data x model, compressed gradient ring)
mesh, each compared with the same step on one device.

There is no CPU fallback: without a TPU the script exits non-zero before any
phase.  A phase that fails raises, and the script exits non-zero.  The last
line of standard output, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "hymba_1_5b"
KERNEL_FORMATS = ("t8", "t16", "e4m3", "mxt8")
KV_LEN = 4096  # decode-attention cache length of the kernel phase

# Kernel contractions, relative to max|ref| (ref in f32 at "highest"): the
# MXU may take f32 operands as bf16 (2**-9 relative rounding per operand).
# Over K = 1600 random-sign products that is ~2**-9 * sqrt(K) / max-factor
# ~ 1e-3 of the largest output; 1e-2 keeps a 10x margin and still fails any
# wrong tile, mask, scale or table entry, whose errors are of order 1.
CONTRACTION_TOL = 1e-2
# Serve logits, rms(got - ref) / rms(ref) at each compared position.  The
# serve path computes in bf16 activations over a t8 KV cache; the reference
# in f32 over exact K/V.  Measured at published widths: 0.017 at 2 layers
# and 0.022 at 8 layers on the CPU (512-token prompts), 0.044 to 0.055 at
# 32 layers on a TPU v5e.  0.1 leaves room for that; wrong weights, a wrong
# layer or a wrong position give errors of order 1.  A KV write that lands
# on the wrong slot moves the logits by only ~0.01-0.03 (the 1024-token
# window dilutes it), so the slot check below is what catches that.
SERVE_LOGIT_TOL = 0.1
# KV-cache slots, rms(decoded slot - ref K/V) / rms(ref K/V) per (layer,
# sequence, position): t8 rounds each element by at most 2**-4 relative near
# magnitude 1 (2**-3 at 2..4), plus the bf16 drift of the residual stream.
# A slot holding another token's K/V scores ~1.4, an unwritten slot 1.0.
SERVE_SLOT_TOL = 0.25
# Four-chip steps against one device.  Loss: bf16 activations re-rounded
# after a different f32 reduction order flip a few roundings of 2**-9; the
# loss averages 16k tokens, so 2e-3 is loose.  Grad norm: the same flips over
# ~4e8 elements, 1e-2.  The compressed ring adds the t16 wire (stochastic
# rounding, at most 2**-7 relative on any element), so 2e-2 there.
LOSS_RTOL = 2e-3
GNORM_RTOL = 1e-2
GNORM_RTOL_RING = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(dev) -> int:
    return dev.memory_stats()["peak_bytes_in_use"]


def bytes_in_use(dev) -> int:
    return dev.memory_stats()["bytes_in_use"]


def device_check(count: int):
    """Report the devices; exit non-zero unless there are ``count`` TPUs."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} device_kind={d.device_kind} "
        f"device_count={len(devs)} jax={jax.__version__}")
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {d.platform!r}); "
            "this script has no CPU fallback"
        )
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return d


def require_custom_call(hlo_text: str, what: str) -> None:
    """The compiled program must run the Pallas kernel itself."""
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled HLO")


# ---------------------------------------------------------------------------
# phase 1: kernels
# ---------------------------------------------------------------------------


def kernel_phase(cfg, *, batch: int = 8, kv_len: int = KV_LEN, seed: int = 0):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.lut import resolve_impl

    if not ops.kernels_enabled():
        raise AssertionError("kernels are disabled (ops.use_kernels(False))")
    K, N = cfg.d_model, cfg.d_ff
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    log(f"kernels: w [{K}, {N}], x [{batch}, {K}], q [{batch}, {H}, {hd}], "
        f"KV [{batch}, {Hkv}, {kv_len}, {hd}]")
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    # weights over 13 binades, so the codecs see every regime they hold
    w = jax.random.normal(ks[0], (K, N)) * jnp.exp2(
        jax.random.randint(ks[1], (K, N), -6, 7).astype(jnp.float32)
    )
    x = jax.random.normal(ks[2], (batch, K))
    q = jax.random.normal(ks[3], (batch, H, hd))
    kv = jax.random.normal(ks[4], (2, batch, Hkv, kv_len, hd))

    def native(fn, *args, what):
        t0 = time.perf_counter()
        c = jax.jit(fn).lower(*args).compile()
        dt = time.perf_counter() - t0
        require_custom_call(c.as_text(), what)
        return c, dt

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn).lower(*args).compile()(*args)

    def rel_err(got, want):
        got, want = np.asarray(got), np.asarray(want)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    for fmt in KERNEL_FORMATS:
        dec_impl = resolve_impl(None, fmt)
        enc_impl = resolve_impl(None, fmt, op="encode")

        c, dt = native(lambda a: ops.encode(a, fmt), w, what=f"encode {fmt}")
        bits = c(w)
        want = reference(lambda a: ref.codec_encode_ref(a, fmt), w)
        if not np.array_equal(np.asarray(bits), np.asarray(want)):
            raise AssertionError(f"encode {fmt}: bits differ from the reference")
        log(f"  encode {fmt} impl={enc_impl} tpu_custom_call compile={dt:.2f}s "
            f"bit-exact vs ref.codec_encode_ref: PASS")

        c, dt = native(lambda b: ops.decode(b, fmt), bits, what=f"decode {fmt}")
        vals = c(bits)
        want = reference(lambda b: ref.codec_decode_ref(b, fmt), bits)
        same = np.array_equal(
            np.asarray(vals).view(np.uint32), np.asarray(want).view(np.uint32)
        )
        if not same:
            raise AssertionError(f"decode {fmt}: values differ from the reference")
        log(f"  decode {fmt} impl={dec_impl} tpu_custom_call compile={dt:.2f}s "
            f"bit-exact vs ref.codec_decode_ref: PASS")

        c, dt = native(lambda a, b: ops.matmul(a, b, fmt), x, bits,
                       what=f"matmul {fmt}")
        err = rel_err(c(x, bits), reference(
            lambda a, b: ref.takum_matmul_ref(a, b, fmt), x, bits))
        if not err <= CONTRACTION_TOL:
            raise AssertionError(f"matmul {fmt}: rel err {err:.3g} > {CONTRACTION_TOL}")
        log(f"  matmul {fmt} impl={dec_impl} tpu_custom_call compile={dt:.2f}s "
            f"max|err|/max|ref|={err:.3g} <= {CONTRACTION_TOL}: PASS")

        kb, vb = (reference(lambda a: ref.codec_encode_ref(a, fmt), kv[i])
                  for i in range(2))
        c, dt = native(lambda a, k, v: ops.decode_attention(a, k, v, fmt),
                       q, kb, vb, what=f"decode_attention {fmt}")
        err = rel_err(c(q, kb, vb), reference(
            lambda a, k, v: ref.decode_attention_ref(a, k, v, fmt), q, kb, vb))
        if not err <= CONTRACTION_TOL:
            raise AssertionError(
                f"decode_attention {fmt}: rel err {err:.3g} > {CONTRACTION_TOL}")
        log(f"  decode_attention {fmt} impl={dec_impl} tpu_custom_call "
            f"compile={dt:.2f}s max|err|/max|ref|={err:.3g} <= "
            f"{CONTRACTION_TOL} (KV payload {tuple(kb.shape)} {kb.dtype}): PASS")


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------


def serve_phase(cfg, *, batch: int = 8, prompt: int = 2048, steps: int = 16,
                n_ref: int = 2, seed: int = 0):
    import dataclasses

    import jax
    import numpy as np

    from repro.dist import step as dstep
    from repro.launch.mesh import parse_mesh
    from repro.models import transformer as T

    log(f"serve: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"vocab={cfg.vocab_size} weights={cfg.quant.weights} "
        f"kv_cache={cfg.quant.kv_cache} activations={cfg.quant.activations}; "
        f"batch={batch} prompt={prompt} decode_steps={steps}")
    mesh = parse_mesh("1x1")
    t0 = time.perf_counter()
    qp = jax.jit(lambda key: dstep.quantize_params(cfg, T.init_params(cfg, key)))(
        jax.random.PRNGKey(seed)
    )
    jax.block_until_ready(qp)
    log(f"  random weights quantised: {time.perf_counter() - t0:.1f}s")
    total = prompt + steps
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, total), 0, cfg.vocab_size
    )

    t0 = time.perf_counter()
    prefill = jax.jit(dstep.make_prefill_step(cfg, mesh, cache_len=total)).lower(
        qp, {"tokens": tokens[:, :prompt]}).compile()
    log(f"  prefill compile: {time.perf_counter() - t0:.1f}s")
    last, cache = prefill(qp, {"tokens": tokens[:, :prompt]})
    t0 = time.perf_counter()
    serve = jax.jit(dstep.make_serve_step(cfg, mesh), donate_argnums=(2,)).lower(
        qp, {"token": tokens[:, prompt]}, cache).compile()
    log(f"  serve step compile: {time.perf_counter() - t0:.1f}s")
    outs = [last]
    for i in range(steps):
        logits, cache = serve(qp, {"token": tokens[:, prompt + i]}, cache)
        outs.append(logits)
    got = np.stack([np.asarray(o) for o in outs], axis=1)  # [B, steps+1, V]
    if not np.isfinite(got).all():
        raise AssertionError("serve: non-finite logits")
    if cache.k.shape[2] != total or int(cache.pos) != total:
        raise AssertionError(
            f"serve: cache holds {cache.k.shape[2]} positions at pos "
            f"{int(cache.pos)}, expected {total}")

    # reference: f32 activations over the same dequantised weights, exact
    # K/V, full-precision matmuls, over the whole token sequence
    ref_cfg = cfg.with_(quant=dataclasses.replace(cfg.quant, activations="f32"))
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: T.forward(
            ref_cfg, dstep.dequantize_params(p), t, collect=True)
        ).lower(qp, tokens[:n_ref]).compile()
    log(f"  reference forward compile: {time.perf_counter() - t0:.1f}s")
    ref_logits, _, (ref_k, ref_v, _, _) = fwd(qp, tokens[:n_ref])
    ref_logits = np.asarray(ref_logits)[:, prompt - 1: total]

    d = got[:n_ref] - ref_logits
    per_pos = np.sqrt((d ** 2).mean(axis=(0, 2))) / np.sqrt(
        (ref_logits ** 2).mean(axis=(0, 2)))
    log(f"  logits vs f32 forward, rms err / rms ref per position "
        f"(prefill last, then {steps} decode steps): "
        + " ".join(f"{e:.4f}" for e in per_pos))
    hd = cfg.resolved_head_dim
    slot_worst = {}
    for name, stored, want in (("K", cache.k, ref_k), ("V", cache.v, ref_v)):
        dec = np.asarray(T._decode_cache(cfg, stored[:, :n_ref], hd))
        want = np.asarray(want)
        err = np.sqrt(((dec - want) ** 2).mean(axis=(3, 4))) / np.sqrt(
            (want ** 2).mean(axis=(3, 4)))  # [L, n_ref, total]
        at = np.unravel_index(int(err.argmax()), err.shape)
        slot_worst[name] = (float(err.max()), tuple(int(i) for i in at))
        log(f"  {name} cache, {err.size} (layer, seq, position) slots vs f32 "
            f"forward: worst rms err / rms ref {err.max():.4f} at {slot_worst[name][1]}")
    # measured in full above; judged here
    worst = float(per_pos.max())
    if not worst <= SERVE_LOGIT_TOL:
        raise AssertionError(f"serve: logits err {worst:.4f} > {SERVE_LOGIT_TOL}")
    log(f"  logits: worst {worst:.4f} <= {SERVE_LOGIT_TOL}: PASS")
    for name, (err, at) in slot_worst.items():
        if not err <= SERVE_SLOT_TOL:
            raise AssertionError(
                f"serve: {name} cache slot (layer, seq, pos)={at} "
                f"err {err:.4f} > {SERVE_SLOT_TOL}")
        log(f"  {name} cache slots: worst {err:.4f} <= {SERVE_SLOT_TOL}: PASS")


# ---------------------------------------------------------------------------
# phase 3: train (and the four-chip phase, which reuses its model)
# ---------------------------------------------------------------------------


def train_model(layers: int, batch: int, seq: int):
    from repro import configs
    from repro.launch import train

    cfg, pipe = train.build(ARCH, smoke=False, policy="takum", seq=seq, batch=batch)
    full = configs.get(ARCH).num_layers
    log(f"model: {cfg.name} cut to {layers} of {full} layers, published widths "
        f"(d_model={cfg.d_model}, d_ff={cfg.d_ff}), takum policy, "
        f"batch={batch} seq={seq}")
    return cfg.with_(num_layers=layers), pipe


def train_phase(*, layers: int = 8, batch: int = 8, seq: int = 2048, steps: int = 6):
    import jax
    import numpy as np

    from repro.launch import train

    cfg, pipe = train_model(layers, batch, seq)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        t0 = time.perf_counter()
        loop, state = train.run(cfg, pipe, steps=steps, mesh="1x1",
                                ckpt_dir=ckpt_dir, ckpt_every=steps, log_every=1)
        log(f"  {steps} steps in {time.perf_counter() - t0:.1f}s "
            f"(first step, compile included: {loop.metrics_history[0]['dt']:.1f}s)")
        losses = [m["loss"] for m in loop.metrics_history]
        gnorms = [m["grad_norm"] for m in loop.metrics_history]
        log("  loss per step: " + " ".join(f"{v:.4f}" for v in losses))
        log("  grad norm per step: " + " ".join(f"{v:.4f}" for v in gnorms))
        if len(losses) != steps or not np.isfinite(losses + gnorms).all():
            raise AssertionError(f"train: losses {losses}, grad norms {gnorms}")
        saved = loop.ckpt.all_steps()
        if saved != [steps]:
            raise AssertionError(f"train: checkpoints at steps {saved}, expected [{steps}]")
        restored = loop.ckpt.restore(steps, state)  # raises on a CRC mismatch
        worst = 0.0
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(restored.params)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            worst = max(worst, float(np.abs(a - b).max() / np.abs(a).max()))
        # the params are stored as t16: 2**-8 bounds its rounding relative
        # to each leaf's largest element
        if not worst <= 2.0 ** -8:
            raise AssertionError(f"train: restored params err {worst:.3g}")
        log(f"  checkpoint at step {steps} ({cfg.quant.checkpoint}) restored, "
            f"CRCs valid, params within {worst:.3g} of the trained state: PASS")


def four_chip_phase(*, layers: int = 8, batch: int = 8, seq: int = 2048):
    import jax
    import numpy as np

    from repro.launch import train

    cfg, pipe = train_model(layers, batch, seq)
    results = {}
    for mesh in ("1x1", "2x2", "2x2x1"):
        # the launcher's step, placement and batches, without its loop's
        # checkpoint (phase 3 covers that)
        step_fn, batch_fn, init_state, _ = train.setup(cfg, pipe, mesh=mesh)
        state = init_state()
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch_fn(0))
        m = {k: float(v) for k, v in metrics.items()}
        log(f"  mesh {mesh}: one step, compile included, "
            f"{time.perf_counter() - t0:.1f}s")
        in_use = [bytes_in_use(d) for d in jax.devices()]
        del state, metrics
        results[mesh] = m
        log(f"  mesh {mesh}: loss={m['loss']:.6f} grad_norm={m['grad_norm']:.6f} "
            f"bytes_in_use per device: {in_use}")
        if mesh != "1x1" and min(in_use) < 0.5 * max(in_use):
            raise AssertionError(f"mesh {mesh}: state not spread over the chips: {in_use}")
    one = results["1x1"]
    for mesh, gtol in (("2x2", GNORM_RTOL), ("2x2x1", GNORM_RTOL_RING)):
        m = results[mesh]
        dl = abs(m["loss"] - one["loss"]) / abs(one["loss"])
        dg = abs(m["grad_norm"] - one["grad_norm"]) / abs(one["grad_norm"])
        if not (np.isfinite([m["loss"], m["grad_norm"]]).all()
                and dl <= LOSS_RTOL and dg <= gtol):
            raise AssertionError(
                f"mesh {mesh} vs one device: loss rel diff {dl:.3g} (tol "
                f"{LOSS_RTOL}), grad norm rel diff {dg:.3g} (tol {gtol})")
        log(f"  mesh {mesh} vs one device: loss rel diff {dl:.3g} <= {LOSS_RTOL}, "
            f"grad norm rel diff {dg:.3g} <= {gtol}: PASS")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on four chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    n = 4 if args.four_chips else 1
    dev = device_check(n)
    log(f"compile cache: {cache_dir}")

    import jax

    from repro import configs
    from repro.quant.policy import POLICIES

    t_all = time.perf_counter()
    phases = []
    if args.four_chips:
        phases.append(("four-chip train step", four_chip_phase))
    else:
        cfg = configs.get(ARCH).with_(quant=POLICIES["takum"])
        phases += [
            ("kernels", lambda: kernel_phase(cfg)),
            ("serve", lambda: serve_phase(cfg)),
            ("train", train_phase),
        ]
    for name, fn in phases:
        t0 = time.perf_counter()
        log(f"== phase {name}")
        fn()
        log(f"== phase {name}: PASS in {time.perf_counter() - t0:.1f}s, "
            f"peak_bytes_in_use={peak_bytes(dev)}")
    log(f"all phases passed in {time.perf_counter() - t_all:.1f}s")
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
