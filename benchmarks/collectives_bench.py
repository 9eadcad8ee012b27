"""Compressed cross-pod all-reduce + pipeline hops: wire bytes and quality.

Runs the wire-compressed ring all-reduce on a fake 8-device mesh in a
subprocess (device count must be set before jax init) for the whole wire
format matrix — takum t8/t16 vs OFP8 e4m3/e5m2 vs bf16 on the *same* ring —
and reports error vs the exact f32 all-reduce, plus the analytic
wire-traffic model used by the roofline's collective term.  The same child
also measures the compressed pipeline stage hops (``pipeline_apply``'s
``wire_fmt`` / ``QuantPolicy.pipe_act`` surface): output error vs exact f32
hops and the per-element hop bytes.  ``--smoke`` shrinks the payload for
CI; the summary lands in ``benchmarks/results/collectives.json`` and is
folded into the perf-trajectory artifact by ``benchmarks/run.py --json``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

RESULTS = os.path.join(os.path.dirname(__file__), "results")

PSUM_FMTS = ("f32", "bf16", "t16", "t8", "e4m3", "e5m2", "mxe4m3", "mxt8")
PIPE_FMTS = ("t8", "t16", "e4m3", "bf16", "mxe4m3")

# the child measures numerics on 8 virtual CPU devices; it is pinned to the
# CPU so that, on a machine with a TPU, it never reaches for the chip that
# the parent process holds
_CHILD = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.dist.collectives import compressed_psum
from repro.dist.pipeline import pipeline_apply

mesh = jax.make_mesh((4, 2), ("pod", "x"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal(%SHAPE%).astype(np.float32))

out = {"psum": {}}
for fmt in %PSUM_FMTS%:
    def f(v, fmt=fmt):
        return compressed_psum(v, "pod", fmt)
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod", None, None),
                              out_specs=P("pod", None, None)))
    got = np.asarray(g(x))
    exact = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
    rms = np.sqrt(np.mean(np.asarray(x) ** 2))  # reduction error vs term scale
    err = np.abs(got - exact) / rms
    out["psum"][fmt] = {
        "max_err_over_rms": float(err.max()),
        "mean_err_over_rms": float(err.mean()),
        "rms_err_over_rms": float(np.sqrt(np.mean(err ** 2))),
    }

# compressed pipeline stage hops (QuantPolicy.pipe_act): 4-stage GPipe
# wavefront, tanh-matmul stages, wire-compressed activations between stages
mesh_p = jax.make_mesh((4, 2), ("pipe", "x"))
Pst, M, mb, d = 4, %PIPE_M%, 4, 32
ws = jnp.asarray(rng.standard_normal((Pst, d, d)).astype(np.float32)) * 0.5
xp = jnp.asarray(rng.standard_normal((M, mb, d)).astype(np.float32))

def stage(w, h):
    return jnp.tanh(h @ w)

ref = np.asarray(pipeline_apply(stage, ws, xp, mesh=mesh_p, axis="pipe"))
rms_ref = np.sqrt(np.mean(ref ** 2))
out["pipe_hop"] = {}
for fmt in %PIPE_FMTS%:
    got = np.asarray(pipeline_apply(stage, ws, xp, mesh=mesh_p, axis="pipe",
                                    wire_fmt=fmt))
    err = np.abs(got - ref) / rms_ref
    out["pipe_hop"][fmt] = {
        "max_err_over_rms": float(err.max()),
        "rms_err_over_rms": float(np.sqrt(np.mean(err ** 2))),
    }
print(json.dumps(out))
"""


def run(smoke: bool = False):
    from repro.dist import collectives

    if getattr(collectives, "IS_STUB", False):  # pragma: no cover
        raise NotImplementedError(
            "repro.dist.collectives is a stub; compressed-psum bench pending"
        )
    os.makedirs(RESULTS, exist_ok=True)
    shape = "(4, 64, 32)" if smoke else "(4, 256, 64)"
    child = (
        _CHILD.replace("%SHAPE%", shape)
        .replace("%PSUM_FMTS%", repr(PSUM_FMTS))
        .replace("%PIPE_FMTS%", repr(PIPE_FMTS))
        .replace("%PIPE_M%", "6" if smoke else "12")
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "../src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-c", child],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    child_out = json.loads(res.stdout.strip().splitlines()[-1])
    quality = child_out["psum"]

    from repro.core.formats import wire_format
    from repro.dist.collectives import wire_bytes_per_element

    narrow = [f for f in PSUM_FMTS if f != "f32"]
    wire = {
        fmt: {f"pods={p}": wire_bytes_per_element(fmt, p) for p in (2, 4, 8)}
        for fmt in PSUM_FMTS
    }
    # headline ratio: wire bytes saved vs the f32 status quo (pod-count free)
    reduction = {
        fmt: wire_bytes_per_element("f32", 2) / wire_bytes_per_element(fmt, 2)
        for fmt in narrow
    }
    pipe_hop = {
        fmt: dict(child_out["pipe_hop"][fmt],
                  hop_bytes_per_el=wire_format(fmt).wire_bits_per_el / 8)
        for fmt in PIPE_FMTS
    }
    summary = {
        "quality_4pod": quality,
        "wire_bytes_per_element": wire,
        "wire_reduction_vs_f32": reduction,
        "pipe_hop": pipe_hop,
        "smoke": smoke,
    }
    with open(os.path.join(RESULTS, "collectives.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    return summary


def main():
    smoke = "--smoke" in sys.argv
    t0 = time.perf_counter()
    summary = run(smoke)
    us = (time.perf_counter() - t0) * 1e6
    q = {f: round(v["max_err_over_rms"], 5) for f, v in summary["quality_4pod"].items()}
    print(f"collectives_compressed_psum,{us:.0f},max_err/rms {q}")
    red = summary["wire_reduction_vs_f32"]
    print(
        f"collectives_wire_bytes,0,f32->t16 {red['t16']:.0f}x | "
        f"f32->t8 {red['t8']:.0f}x | f32->e4m3 {red['e4m3']:.0f}x | "
        f"per-element {summary['wire_bytes_per_element']}"
    )
    ph = {f: round(v["rms_err_over_rms"], 5) for f, v in summary["pipe_hop"].items()}
    print(f"collectives_pipe_hop,0,rms_err/rms {ph}")


if __name__ == "__main__":
    main()
