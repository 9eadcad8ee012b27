"""Produce a real Perfetto/Chrome trace + JSONL metrics from a captured run.

Runs a short observed workload — two multi-pod train steps (4x2x1 mesh on
fake CPU devices), an eager kernel-dispatch codec round-trip, and a
compressed-pipeline hop — under one ``telemetry.capture`` scope, then
exports the capture through both ``repro.obs`` exporters:

    benchmarks/results/obs.jsonl        (structured metrics, one JSON/line)
    benchmarks/results/obs_trace.json   (Chrome Trace Event JSON — open in
                                         https://ui.perfetto.dev)

CI archives both as workflow artifacts, so every run leaves an inspectable
timeline of kernel-dispatch, collective-hop, and train-step spans.

    python -m benchmarks.obs_trace_demo
"""

import os

# must precede the jax import: the pod mesh needs 8 virtual CPU devices, and
# the demo stays on the CPU even where a TPU is attached
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")


def main() -> None:
    from repro import configs, obs
    from repro.core import telemetry
    from repro.data import SyntheticLM
    from repro.dist import sharding as shd
    from repro.dist import step as dstep
    from repro.dist.pipeline import pipeline_apply
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.optim import adamw_init
    from repro.quant.policy import QuantPolicy

    mesh = jax.make_mesh((4, 2, 1), ("pod", "data", "model"))
    cfg = configs.get_smoke("llama3_8b").with_(
        quant=QuantPolicy(grad_comm="t8", opt_state="t16")
    )
    pipe = SyntheticLM(cfg.vocab_size, 32, 8, seed=5)
    batch = pipe.batch(0)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = dstep.TrainState(
        params=params, opt=adamw_init(params, fmt=cfg.quant.opt_state),
        rng=jax.random.PRNGKey(1),
    )
    state = jax.device_put(
        state, shd.named(mesh, dstep.train_state_specs_nopod(cfg, mesh))
    )
    batch = jax.device_put(
        batch, shd.named(mesh, shd.batch_specs(cfg, mesh, kind="train", batch=8))
    )
    step = jax.jit(dstep.make_train_step(cfg, mesh))

    pmesh = jax.make_mesh((4,), ("pipe",))
    sw = jnp.stack([jnp.eye(16) * (1.0 + 0.01 * i) for i in range(4)])
    px = jax.random.normal(jax.random.PRNGKey(2), (8, 4, 16))
    x = jax.random.normal(jax.random.PRNGKey(3), (256, 256))

    with telemetry.capture():
        for _ in range(2):
            with telemetry.host_span("loop.step", cat="step"):
                state, metrics = step(state, batch)
                jax.block_until_ready(metrics["loss"])
        dec = ops.decode(ops.encode(x, "t8"), "t8")
        py = pipeline_apply(
            lambda w, h: h @ w, sw, px, mesh=pmesh, wire_fmt="t8"
        )
        jax.block_until_ready((dec, py))

    os.makedirs(RESULTS, exist_ok=True)
    jsonl = os.path.join(RESULTS, "obs.jsonl")
    trace = os.path.join(RESULTS, "obs_trace.json")
    n_lines = obs.export_jsonl(jsonl)
    n_spans = obs.export_chrome_trace(trace)
    evs = obs.validate_chrome_trace(obs.load_chrome_trace(trace))
    cats = sorted({e["cat"] for e in evs})
    assert {"kernel", "collective", "step"} <= set(cats), cats
    print(f"obs_trace_demo_jsonl,0,{n_lines} lines {os.path.relpath(jsonl)}")
    print(f"obs_trace_demo_trace,0,{n_spans} spans cats={'|'.join(cats)} "
          f"{os.path.relpath(trace)}")


if __name__ == "__main__":
    main()
