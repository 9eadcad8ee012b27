"""Distribution-layer tests on an 8-fake-device mesh (subprocess: device
count must be fixed before jax initialises).

Covers: sharding-rule shape validity, a REAL multi-device train step
(numerics equal to single-device), compressed cross-pod psum quality, and a
small-mesh dry-run (lower+compile with memory/cost extraction) — the CI-sized
version of the production dry-run.
"""

import json
import os
import subprocess
import sys

import pytest

pytest.importorskip("repro.dist.collectives")

_SRC = os.path.join(os.path.dirname(__file__), "../src")


def _run(child: str, timeout=500) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    res = subprocess.run(
        [sys.executable, "-c", child], env=env, capture_output=True, text=True, timeout=timeout
    )
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-3000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


_PRE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
"""


def test_sharded_train_step_matches_single_device():
    out = _run(_PRE + """
from repro import configs
from repro.dist import sharding as shd, step as dstep
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.data import SyntheticLM

cfg = configs.get_smoke("llama3_8b")
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
pipe = SyntheticLM(cfg.vocab_size, 32, 4, seed=5)
batch = pipe.batch(0)

def init():
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return dstep.TrainState(params=params, opt=adamw_init(params, fmt=cfg.quant.opt_state),
                            rng=jax.random.PRNGKey(1))

state = init()
step = dstep.make_train_step(cfg, mesh)

# single device reference
s1, m1 = jax.jit(step)(state, batch)

# sharded
sspec = dstep.train_state_specs(cfg, mesh)
bspec = shd.batch_specs(cfg, mesh, kind="train", batch=4)
fn = jax.jit(step, in_shardings=(shd.named(mesh, sspec), shd.named(mesh, bspec)),
             out_shardings=(shd.named(mesh, sspec), None))
state_sh = jax.device_put(state, shd.named(mesh, sspec))
batch_sh = jax.device_put(batch, shd.named(mesh, bspec))
s2, m2 = fn(state_sh, batch_sh)

d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) if hasattr(a, 'dtype') and a.dtype != jnp.uint16 else 0.0, s1.params, s2.params)
maxd = max(jax.tree.leaves(d))
print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]), "max_param_diff": maxd}))
""")
    assert abs(out["loss1"] - out["loss2"]) < 1e-2, out
    assert out["max_param_diff"] < 1e-2, out


def test_compressed_psum_quality_and_exactness():
    out = _run(_PRE + """
from repro.dist.collectives import compressed_psum
mesh = jax.make_mesh((4, 2), ("pod", "x"))
x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64, 32)).astype(np.float32))
res = {}
rms = float(np.sqrt(np.mean(np.asarray(x) ** 2)))
for fmt in ("f32", "t16", "t8", "bf16", "e4m3", "e5m2", "mxe4m3", "mxt8"):
    f = jax.jit(jax.shard_map(lambda v, fmt=fmt: compressed_psum(v, "pod", fmt),
                mesh=mesh,
                in_specs=P("pod", None, None), out_specs=P("pod", None, None)))
    got = np.asarray(f(x))
    exact = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
    # normalise by input RMS (sums can be ~0 while terms are O(1), so
    # pointwise relative error is the wrong metric for a reduction)
    res[fmt] = float(np.max(np.abs(got - exact)) / rms)
# block codec pad/slice: a last dim that is NOT a 32-multiple rides the
# same ring (padded in, sliced out, shape preserved)
xo = x[..., :27]
f = jax.jit(jax.shard_map(lambda v: compressed_psum(v, "pod", "mxe4m3"),
            mesh=mesh,
            in_specs=P("pod", None, None), out_specs=P("pod", None, None)))
go = np.asarray(f(xo))
assert go.shape == xo.shape
res["mx_unaligned"] = float(np.max(np.abs(
    go - np.broadcast_to(np.asarray(xo).sum(0, keepdims=True), xo.shape))) / rms)
print(json.dumps(res))
""")
    assert out["f32"] < 1e-6
    assert out["t16"] < 2e-2  # P-1=3 terms quantised at <=2**-9 of magnitude
    assert out["t8"] < 1.0  # tapered 8-bit: ~2**-3 per term worst-case
    assert out["bf16"] < 4e-2  # 8-bit mantissa wire
    assert out["e4m3"] < 1.0  # 3-bit mantissa: ~2**-4 per term in-range
    assert out["e5m2"] < 1.5  # 2-bit mantissa: the zoo's grad wire
    # block-scaled wires: the shared E8M0 scale recovers the dynamic range
    # the flat OFP8 wire spends exponent bits on
    assert out["mxe4m3"] < 1.0 and out["mxt8"] < 1.0
    assert out["mx_unaligned"] < 1.0
    # the paper's ordering on a unit-normal payload: t8 beats e5m2 at equal
    # width, t16 beats bf16's error by construction (denser taper near 1)
    assert out["t8"] < out["e5m2"]


def test_multipod_compressed_train_step_compiles_and_runs():
    out = _run(_PRE + """
from repro import configs
from repro.dist import sharding as shd, step as dstep
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.data import SyntheticLM
from repro.quant.policy import QuantPolicy

cfg = configs.get_smoke("llama3_8b").with_(quant=QuantPolicy(
    grad_comm="t16", opt_state="t16"))
# model=1: XLA's PartitionGather aborts (SIGABRT, upstream bug) when the
# embedding gather meets a manual pod axis on tiny model-sharded meshes;
# the production 2x16x16 mesh compiles this exact path (pod2 dry-run sweep),
# so the test pins the pod-compression machinery with TP disabled.
mesh = jax.make_mesh((2, 4, 1), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
pipe = SyntheticLM(cfg.vocab_size, 32, 4, seed=5)
batch = pipe.batch(0)
params = T.init_params(cfg, jax.random.PRNGKey(0))
state = dstep.TrainState(params=params, opt=adamw_init(params, fmt="t16"),
                         rng=jax.random.PRNGKey(1))
step = dstep.make_train_step(cfg, mesh)
specs = dstep.train_state_specs_nopod(cfg, mesh)
bspec = shd.batch_specs(cfg, mesh, kind="train", batch=4)
state = jax.device_put(state, shd.named(mesh, specs))
batch = jax.device_put(batch, shd.named(mesh, bspec))
s2, m = jax.jit(step)(state, batch)
l0 = float(m["loss"])
s3, m2 = jax.jit(step)(s2, batch)
print(json.dumps({"loss0": l0, "loss1": float(m2["loss"])}))
""")
    assert out["loss1"] < out["loss0"], out  # same batch twice: loss must drop


def test_small_mesh_dryrun_cells():
    """CI-sized dry-run: every family on a 2x4 mesh, lower+compile, and the
    collective-bytes parser returns nonzero traffic for sharded cells."""
    out = _run(_PRE + """
from repro import configs
from repro.launch import dryrun
for arch, shape in [("llama3_2_3b", "decode_32k"), ("mamba2_780m", "long_500k")]:
    cfg = configs.get_smoke(arch)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rec = dryrun.run_cell("musicgen_large", "train_4k", multi_pod=False, mesh=mesh)
ok1 = rec["collectives"]["total_bytes"] > 0 and rec["cost"]["flops"] > 0
rec2 = dryrun.run_cell("hymba_1_5b", "long_500k", multi_pod=False, mesh=mesh)
ok2 = "error" not in rec2
print(json.dumps({"ok1": bool(ok1), "ok2": bool(ok2)}))
""", timeout=560)
    assert out["ok1"] and out["ok2"]


def test_pipeline_parallel_matches_sequential():
    out = _run(_PRE + """
from repro.dist.pipeline import pipeline_apply
mesh = jax.make_mesh((4, 2), ("pipe", "x"))
P_st, M, mb, d = 4, 6, 3, 16
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.standard_normal((P_st, d, d)).astype(np.float32)) * 0.5
x = jnp.asarray(rng.standard_normal((M, mb, d)).astype(np.float32))

def stage(w, h):
    return jnp.tanh(h @ w)

got = np.asarray(pipeline_apply(stage, ws, x, mesh=mesh, axis="pipe"))
ref = np.asarray(x)
for i in range(P_st):
    ref = np.tanh(ref @ np.asarray(ws[i]))
err = float(np.abs(got - ref).max())
print(json.dumps({"err": err}))
""")
    assert out["err"] < 1e-5, out


def test_pipeline_compressed_hops_quality():
    """wire_fmt compresses the inter-stage activation hops (QuantPolicy's
    pipe_act surface): outputs stay close to the exact-f32-hop pipeline,
    tighter for 16-bit wires than 8-bit, and bit-exact for wire_fmt=None."""
    out = _run(_PRE + """
from repro.dist.pipeline import pipeline_apply
mesh = jax.make_mesh((4, 2), ("pipe", "x"))
P_st, M, mb, d = 4, 6, 3, 16
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.standard_normal((P_st, d, d)).astype(np.float32)) * 0.5
x = jnp.asarray(rng.standard_normal((M, mb, d)).astype(np.float32))

def stage(w, h):
    return jnp.tanh(h @ w)

ref = np.asarray(pipeline_apply(stage, ws, x, mesh=mesh, axis="pipe"))
rms = float(np.sqrt(np.mean(ref ** 2)))
res = {}
for fmt in ("t8", "t16", "e4m3", "bf16", "mxe4m3", "mxt8"):
    got = np.asarray(pipeline_apply(stage, ws, x, mesh=mesh, axis="pipe",
                                    wire_fmt=fmt))
    res[fmt] = float(np.abs(got - ref).max() / rms)
print(json.dumps(res))
""")
    # 3 compressed hops, tanh-bounded activations: one quantisation error
    # per element per hop, amplified by at most ||w|| per stage
    assert out["t8"] < 0.5, out
    assert out["e4m3"] < 0.5, out
    assert out["t16"] < 2e-2, out
    assert out["bf16"] < 4e-2, out
    assert out["t16"] < out["t8"]  # width ordering sanity
    # block-scaled hops ride the same codec, with the pad/slice wrapper
    # active here (d = 16 is not a 32-multiple).  The bound is looser than
    # flat e4m3's: the MX absmax clamp (scaled block max in [448, 512)
    # saturates to 448, OCP's own conversion rule) costs up to 12.5% on
    # each block's largest element — tanh activations keep every element
    # inside flat e4m3's range, so the container buys nothing here and
    # pays the clamp; the psum test above shows the opposite regime
    assert out["mxe4m3"] < 1.0 and out["mxt8"] < 1.0, out
