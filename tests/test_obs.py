"""repro.obs: the unified observability subsystem (DESIGN.md §9).

In-process tests cover the metrics registry (counters/gauges/histograms,
nested capture scopes, thread-safety, the zero-op trace-time gate), the
statistics core (seeded bootstrap CIs + the CI-overlap gate), and the
JSONL / Chrome-trace exports with parse-back.  Multiplicity under
shard_map and the end-to-end acceptance (captured multi-pod train step ->
spans -> trace export -> parse-back) need real devices and trace-cache
isolation, so they run in subprocesses on an 8-fake-device mesh (same
idiom as tests/test_faults.py).
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

pytest.importorskip("repro.core.telemetry")

import jax
import jax.numpy as jnp

from repro.core import telemetry
from repro.obs import stats as obstats
from repro.obs import trace_export

_SRC = os.path.join(os.path.dirname(__file__), "../src")


def _run(child: str, timeout=500) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    res = subprocess.run(
        [sys.executable, "-c", child], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-3000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


_PRE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
"""


# ---------------------------------------------------------------------------
# registry: kinds, scopes, gates
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_hist_roundtrip():
    with telemetry.capture():
        telemetry.record("t.c", 2.0)
        telemetry.record("t.c", 3.0)
        telemetry.record_gauge("t.g", 1.0)
        telemetry.record_gauge("t.g", 7.5)  # last write wins
        for v in (1.0, 2.0, 3.0, 4.0):
            telemetry.record_hist("t.h", v)
        snap = telemetry.snapshot()
    assert snap["counters"]["t.c"] == 5.0
    assert snap["gauges"]["t.g"] == 7.5
    h = snap["hists"]["t.h"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0 and h["mean"] == 2.5


def test_records_dropped_outside_capture():
    telemetry.record("t.outside", 1.0)
    telemetry.record_gauge("t.outside", 1.0)
    telemetry.record_hist("t.outside", 1.0)
    with telemetry.capture():
        assert "t.outside" not in telemetry.counters()
        assert "t.outside" not in telemetry.gauges()
        assert "t.outside" not in telemetry.hists()


def test_nested_capture_scopes_share_one_store():
    with telemetry.capture() as outer:
        telemetry.record("t.n", 1.0)
        with telemetry.capture() as inner:
            # nested scope: same live store, NO reset of accumulated state
            assert inner is outer
            assert telemetry.counters()["t.n"] == 1.0
            telemetry.record("t.n", 1.0)
        # inner exit leaves the outer scope recording
        assert telemetry.enabled()
        telemetry.record("t.n", 1.0)
        assert telemetry.counters()["t.n"] == 3.0
    assert not telemetry.enabled()
    # a fresh outermost scope resets
    with telemetry.capture():
        assert "t.n" not in telemetry.counters()


def test_capture_fresh_false_preserves_state():
    with telemetry.capture():
        telemetry.record("t.keep", 1.0)
    with telemetry.capture(fresh=False):
        assert telemetry.counters()["t.keep"] == 1.0


def test_hist_decimation_keeps_exact_moments_and_bounded_sample():
    n = 3 * telemetry._Hist.CAP
    with telemetry.capture():
        for i in range(n):
            telemetry.record_hist("t.big", float(i))
        h = telemetry.snapshot()["hists"]["t.big"]
    assert h["count"] == n
    assert h["sum"] == sum(range(n))
    assert h["min"] == 0.0 and h["max"] == float(n - 1)
    # quantiles come from the decimated sample: bounded but still spread
    # over the whole window
    assert 0.4 * n < h["p50"] < 0.6 * n
    assert h["p99"] > 0.9 * n


def test_registry_thread_safety_under_concurrent_records():
    threads, per = 8, 1000

    def work(i):
        for k in range(per):
            telemetry.record("t.mt", 1.0)
            telemetry.record_hist("t.mt.h", float(k))

    with telemetry.capture():
        ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = telemetry.snapshot()
    assert snap["counters"]["t.mt"] == float(threads * per)
    assert snap["hists"]["t.mt.h"]["count"] == threads * per


def test_host_span_records_wall_clock_and_args():
    with telemetry.capture():
        with telemetry.host_span("t.host", cat="step", step=3):
            pass
        (sp,) = [s for s in telemetry.spans() if s["name"] == "t.host"]
    assert sp["cat"] == "step" and sp["t1"] >= sp["t0"]
    assert sp["args"] == {"step": 3}


def test_probe_is_one_element():
    assert telemetry.probe(jnp.ones((4, 5))).size == 1
    assert telemetry.probe(jnp.zeros((0,))).size == 1


# ---------------------------------------------------------------------------
# the zero-op trace-time gate (acceptance: asserted on the jaxpr)
# ---------------------------------------------------------------------------


def _make_instrumented():
    # a FRESH function per test: jax caches traces structurally on the
    # function object, so sharing one across tests would let an uncaptured
    # (callback-free) trace shadow a captured one and vice versa
    def instrumented(x):
        telemetry.emit("z.c", jnp.sum(x))
        telemetry.emit_gauge("z.g", jnp.max(x))
        telemetry.emit_hist("z.h", jnp.min(x))
        with telemetry.trace_span("z.s", cat="kernel") as sp:
            y = x * 2
            sp.dep = telemetry.probe(y)
        return y

    return instrumented


def test_uncaptured_trace_carries_zero_callback_ops():
    jaxpr = str(jax.make_jaxpr(_make_instrumented())(jnp.ones(8)))
    assert "callback" not in jaxpr
    # not merely gated callbacks: NO leftover instrumentation ops at all —
    # the jaxpr is exactly the payload computation
    assert jaxpr.count("mul") == 1


def test_captured_trace_carries_the_callbacks():
    with telemetry.capture():
        jaxpr = str(jax.make_jaxpr(_make_instrumented())(jnp.ones(8)))
    assert "callback" in jaxpr


def test_emissions_flushed_by_capture_exit():
    with telemetry.capture() as ctrs:
        f = jax.jit(_make_instrumented())
        jax.block_until_ready(f(jnp.ones(8)))
        jax.block_until_ready(f(jnp.full(8, 2.0)))
    # exit ran jax.effects_barrier(): both executions' emissions landed
    assert ctrs["z.c"] == 24.0
    assert telemetry.gauges()["z.g"] == 2.0
    assert telemetry.hists()["z.h"]["count"] == 2
    spans = [s for s in telemetry.spans() if s["name"] == "z.s"]
    assert len(spans) + telemetry.dropped_spans() >= 2


# ---------------------------------------------------------------------------
# stats core: seeded bootstrap + CI-overlap gate
# ---------------------------------------------------------------------------


def test_bootstrap_ci_is_deterministic_and_brackets_the_median():
    rng = np.random.default_rng(7)
    s = rng.normal(100.0, 5.0, size=11)
    a = obstats.bootstrap_ci(s)
    b = obstats.bootstrap_ci(s)
    assert a == b, "seeded bootstrap must be bit-identical across runs"
    lo, hi = a
    assert lo <= np.median(s) <= hi
    assert lo < hi


def test_bootstrap_ci_degenerate_sizes():
    assert obstats.bootstrap_ci([5.0]) == (5.0, 5.0)
    lo, hi = obstats.bootstrap_ci([])
    assert np.isnan(lo) and np.isnan(hi)


def test_summarize_schema():
    st = obstats.summarize([1.0, 2.0, 3.0, 4.0, 5.0])
    assert st["median"] == 3.0 and st["reps"] == 5
    assert st["ci_lo"] <= st["median"] <= st["ci_hi"]
    assert {"mean", "min", "max"} <= st.keys()


def test_ci_gate_statuses():
    base = {"median": 100.0, "ci_lo": 95.0, "ci_hi": 105.0}
    # overlapping CIs: within noise regardless of the point ratio
    g = obstats.ci_gate(base, {"median": 85.0, "ci_lo": 70.0, "ci_hi": 100.0})
    assert g["status"] == "ok" and not g["separated"]
    # disjoint below + > min-effect drop: regression
    g = obstats.ci_gate(base, {"median": 70.0, "ci_lo": 65.0, "ci_hi": 75.0})
    assert g["status"] == "regression" and g["separated"]
    # disjoint but sub-effect-size: real, tiny, not a failure
    g = obstats.ci_gate(
        {"median": 100.0, "ci_lo": 99.0, "ci_hi": 101.0},
        {"median": 97.0, "ci_lo": 96.0, "ci_hi": 96.9},
    )
    assert g["status"] == "ok" and g["separated"]
    # the mirror image: improvement
    g = obstats.ci_gate(base, {"median": 130.0, "ci_lo": 120.0, "ci_hi": 140.0})
    assert g["status"] == "improvement"


# ---------------------------------------------------------------------------
# exports: JSONL + Chrome trace, with parse-back
# ---------------------------------------------------------------------------


def _populate():
    telemetry.record("e.c", 2.0)
    telemetry.record_gauge("e.g", 1.5)
    telemetry.record_hist("e.h", 3.0)
    with telemetry.host_span("e.span", cat="step", step=1):
        pass


def test_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "obs.jsonl")
    with telemetry.capture():
        _populate()
        n = trace_export.export_jsonl(path)
    lines = trace_export.load_jsonl(path)
    assert len(lines) == n == 4
    by_kind = {l["kind"]: l for l in lines}
    assert by_kind["counter"]["tag"] == "e.c" and by_kind["counter"]["value"] == 2.0
    assert by_kind["gauge"]["value"] == 1.5
    assert by_kind["hist"]["count"] == 1
    assert by_kind["span"]["name"] == "e.span" and by_kind["span"]["dur_us"] >= 0


def test_chrome_trace_roundtrip(tmp_path):
    path = str(tmp_path / "trace.json")
    with telemetry.capture():
        _populate()
        n = trace_export.export_chrome_trace(path)
    trace = trace_export.load_chrome_trace(path)
    evs = trace_export.validate_chrome_trace(trace)
    assert len(evs) == n == 1
    (ev,) = evs
    assert ev["name"] == "e.span" and ev["cat"] == "step"
    assert ev["ts"] == 0.0 and ev["dur"] >= 0.0
    assert trace["otherData"]["counters"]["e.c"] == 2.0


def test_validate_chrome_trace_rejects_garbage():
    with pytest.raises(AssertionError):
        trace_export.validate_chrome_trace({"foo": 1})
    with pytest.raises(AssertionError):
        trace_export.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 1}]}
        )


# ---------------------------------------------------------------------------
# shard_map multiplicity (subprocess: 8 fake devices)
# ---------------------------------------------------------------------------


def test_shard_map_multiplicity_counters_hists_spans():
    out = _run(_PRE + """
from repro.core import telemetry

mesh = jax.make_mesh((8,), ("x",))

def body(x):
    telemetry.emit("m.count", jnp.float32(1))
    telemetry.emit_hist("m.h", jnp.sum(x))
    with telemetry.trace_span("m.span", cat="test") as sp:
        y = x * 2
        sp.dep = telemetry.probe(y)
    return y

with telemetry.capture() as ctrs:
    f = jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                      check_vma=False)
    jax.block_until_ready(jax.jit(f)(jnp.arange(16.0)))

snap = telemetry.snapshot()
print(json.dumps({
    "count": snap["counters"]["m.count"],
    "hist_count": snap["hists"]["m.h"]["count"],
    "spans": len([s for s in snap["spans"] if s["name"] == "m.span"]),
    "dropped": snap["dropped_spans"],
}))
""")
    # every device emits: counters sum 8 ones, the hist takes 8 samples,
    # and 8 begin/end pairs arrive (an end racing ahead of its begin is
    # counted as dropped, never silently lost)
    assert out["count"] == 8.0
    assert out["hist_count"] == 8
    assert out["spans"] + out["dropped"] == 8


# ---------------------------------------------------------------------------
# end-to-end acceptance: captured pod train step -> spans -> exports
# ---------------------------------------------------------------------------


def test_e2e_capture_train_step_export_parse_back(tmp_path):
    jsonl = str(tmp_path / "obs.jsonl")
    trace = str(tmp_path / "obs_trace.json")
    out = _run(_PRE + f"""
from repro.core import telemetry
from repro import configs, obs
from repro.dist import step as dstep, sharding as shd
from repro.data import SyntheticLM
from repro.kernels import ops
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.quant.policy import QuantPolicy

mesh = jax.make_mesh((4, 2, 1), ("pod", "data", "model"))
cfg = configs.get_smoke("llama3_8b").with_(
    quant=QuantPolicy(grad_comm="t8", opt_state="t16"))
pipe = SyntheticLM(cfg.vocab_size, 32, 8, seed=5)
batch = pipe.batch(0)
params = T.init_params(cfg, jax.random.PRNGKey(0))
state = dstep.TrainState(params=params,
                         opt=adamw_init(params, fmt=cfg.quant.opt_state),
                         rng=jax.random.PRNGKey(1))
specs = dstep.train_state_specs_nopod(cfg, mesh)
bspec = shd.batch_specs(cfg, mesh, kind="train", batch=8)
state = jax.device_put(state, shd.named(mesh, specs))
batch = jax.device_put(batch, shd.named(mesh, bspec))
step = jax.jit(dstep.make_train_step(cfg, mesh))

x = jax.random.normal(jax.random.PRNGKey(2), (64, 128))
with telemetry.capture() as ctrs:
    state, m = step(state, batch)
    dec = ops.decode(ops.encode(x, "t8"), "t8")
    jax.block_until_ready((m["loss"], dec))

n_jsonl = obs.export_jsonl({jsonl!r})
n_spans = obs.export_chrome_trace({trace!r})
evs = obs.validate_chrome_trace(obs.load_chrome_trace({trace!r}))
lines = obs.load_jsonl({jsonl!r})
snap = telemetry.snapshot()
print(json.dumps({{
    "cats": sorted({{e["cat"] for e in evs}}),
    "names": sorted({{e["name"] for e in evs}}),
    "n_spans": n_spans,
    "n_jsonl": n_jsonl,
    "jsonl_kinds": sorted({{l["kind"] for l in lines}}),
    "kernel_calls": snap["counters"].get("kernel.calls.decode.t8", 0.0),
    "wire_hops": snap["counters"].get("wire.hops", 0.0),
    "grad_leaves": len(jax.tree.leaves(params)),
    "step_calls": snap["counters"].get("step.calls", 0.0),
    "grad_norm_count": snap["hists"]["step.grad_norm"]["count"],
}}))
""")
    # acceptance: the trace holds kernel-dispatch, collective-hop, AND
    # train-step spans, and both exports parse back
    assert {"kernel", "collective", "step"} <= set(out["cats"]), out
    assert any(n.startswith("kernel.decode") for n in out["names"]), out
    assert any(n.startswith("wire.hop") for n in out["names"]), out
    assert "step.train" in out["names"], out
    assert out["n_spans"] >= 3
    assert {"counter", "hist", "span"} <= set(out["jsonl_kinds"]), out
    # online metrics wired through the same capture
    assert out["kernel_calls"] == 1.0  # eager dispatch: multiplicity 1
    # one ring per gradient leaf: (N-1)=3 hops x 8 devices each
    assert out["wire_hops"] == 24.0 * out["grad_leaves"]
    assert out["step_calls"] == 1.0
    assert out["grad_norm_count"] == 1
