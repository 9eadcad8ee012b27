"""Device time by named scope: the reduction on small synthesised traces
with hand-worked answers, and the program's train and decode steps at a
test size carrying the scopes, with no other change to the program.

Run: ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

import tiny
from bench import harness, program, scopes, traffic, weights
from bench.weights import seed_key

HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="state.params"}
  %while.1 = (f32[8]{0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(layers)/while"}
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/jvp(layers)/while/body/closed_call/norm/mul" stack_frame_id=3}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/jvp(layers)/while/body/closed_call/mamba.ssd/jit(softplus)/add"}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/mamba.ssd/mul"}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f4, metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/rematted_computation/mamba.ssd/exp"}
  %copy.5 = f32[8]{0} copy(%p)
  %copy.6 = f32[8]{0} copy(%p)
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f7, metadata={op_name="jit(step)/jit(_threefry_split)/xor"}
  ROOT %fusion.8 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f8, metadata={op_name="jit(step)/optimizer/mul"}
}
"""

# (instruction, start ms, duration ms) on the device, inside a 20 ms window
OPS = [("while.1", 1, 10), ("fusion.1", 1, 1), ("fusion.2", 2, 2), ("fusion.3", 4, 1),
       ("fusion.4", 5, 1.5), ("copy.5", 7, 2), ("copy.6", 11, 1), ("fusion.7", 12, 0.5),
       ("fusion.8", 13, 2),
       ("fusion.1", 16, 1),  # the same name in a program the harness did not compile
       ("fusion.8", 21, 1)]  # after the window: left out
MODULES = [("jit_step(7)", 0, 15), ("jit_make(9)", 16, 2)]
WINDOW = [("bench.window", 0, 20)]


def _events(evs, meta, stats=None):
    out = []
    for i, (n, s, d) in enumerate(evs):
        st = "" if stats is None else (
            f' stats {{ metadata_id: 1000 str_value: "{stats[i]}" }}')
        out.append(f"    events {{ metadata_id: {meta[n]} offset_ps: {int(s * 1e9)} "
                   f"duration_ps: {int(d * 1e9)}{st} }}")
    return out


def _plane(pid, name, lines, stats=None):
    """lines: {line name: [(event name, start ms, duration ms)]}; ``stats``:
    {line name: [hlo_module stat of each event]}"""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        out.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        out += _events(evs, meta, (stats or {}).get(lname))
        out.append("  }")
    for n, i in meta.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}')
    out.append('  stat_metadata { key: 1000 value { id: 1000 name: "hlo_module" } }')
    out.append("}")
    return "\n".join(out)


def _trace(devices, stats=None):
    parts = [_plane(i + 1, f"/device:TPU:{i}", lines, stats) for i, lines in enumerate(devices)]
    parts.append(_plane(99, "/host:CPU", {"python3": WINDOW}))
    return ProfileData.from_text_proto("\n".join(parts))


def _ms(d):
    return {k: pytest.approx(v * 1e-3) for k, v in d.items()}


def test_op_names_read_the_compiled_text():
    names = scopes.op_names(HLO)
    assert names[("jit_step", "fusion.8")] == "jit(step)/optimizer/mul"
    assert names[("jit_step", "fusion.1")].endswith("/norm/mul")
    assert names[("jit_step", "copy.5")] is None  # no metadata
    assert ("jit_step", "main.9") not in names  # a computation, not an instruction


FUSED = """HloModule jit_step, is_scheduled=true

%fused_a (p0: f32[8]) -> f32[1,8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/layers/while/body/mamba.ssm_update/mul"}
  %add.2 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(step)/layers/while/body/mamba.ssm_update/add"}
  ROOT %broadcast.3 = f32[1,8]{1,0} broadcast(%add.2), dimensions={1}, metadata={op_name="jit(step)/layers/while/body/broadcast_in_dim"}
}

%fused_b (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %convolution.4 = f32[8,8]{1,0} convolution(%p0, %p0), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/mamba.in_proj/dot_general"}
  ROOT %multiply.5 = f32[8,8]{1,0} multiply(%convolution.4, %p0), metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/norm/mul"}
}

%fused_c (p0: f32[8,8]) -> (f32[8,8], f32[8,8]) {
  %p0 = f32[8,8]{1,0} parameter(0)
  %fusion.6 = f32[8,8]{1,0} fusion(%p0), kind=kOutput, calls=%fused_b, metadata={op_name="jit(step)/norm/mul"}
  %subtract.7 = f32[8,8]{1,0} subtract(%p0, %p0), metadata={op_name="jit(step)/norm/sub"}
  %negate.8 = f32[8,8]{1,0} negate(%p0), metadata={op_name="jit(step)/norm/neg"}
  ROOT %tuple.9 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%fusion.6, %subtract.7)
}

ENTRY %main (p: f32[8]) -> f32[1,8] {
  %p = f32[8]{0} parameter(0)
  %q = f32[8,8]{1,0} parameter(1)
  %fusion.10 = f32[1,8]{1,0:T(8,128)} fusion(%p), kind=kLoop, calls=%fused_a, metadata={op_name="jit(step)/layers/while/body/broadcast_in_dim"}
  %fusion.11 = f32[8,8]{1,0} fusion(%q), kind=kOutput, calls=%fused_b, metadata={op_name="jit(step)/transpose(jvp(layers))/while/body/norm/mul"}
  %fusion.12 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(%q), kind=kLoop, calls=%fused_c
  ROOT %copy.13 = f32[1,8]{1,0} copy(%fusion.10)
}
"""


def test_a_fusion_takes_the_scope_of_the_work_in_it():
    names = scopes.op_names(FUSED)
    scope = lambda n: scopes.scope_of(names[("jit_step", n)])
    # the majority of the fused instructions, not the stacking broadcast
    assert scope("fusion.10") == "mamba.ssm_update"
    # the matmul, not the elementwise gradient fused after it
    assert scope("fusion.11") == "mamba.in_proj"
    assert scopes.phase_of(names[("jit_step", "fusion.11")]) == "bwd"
    # a matmul in a nested fusion wins over the majority around it
    assert scope("fusion.12") == "mamba.in_proj"
    assert names[("jit_step", "copy.13")] is None


def test_scope_of_takes_the_innermost_program_scope():
    assert scopes.scope_of("jit(step)/transpose(jvp(layers))/while/body/mamba.ssd/mul") \
        == "mamba.ssd"
    assert scopes.scope_of("jit(step)/jvp(layers)/while/body/dynamic_slice") == "layers"
    assert scopes.scope_of("jit(step)/layers/while/body/kernel.matmul.t8/pallas_call") \
        == "kernel.matmul.t8"
    assert scopes.scope_of("wire.ring.t8/wire.hop.t8/ppermute") == "wire.hop.t8"
    # XLA joins merged ops' paths with ";": the first is read
    assert scopes.scope_of("head/dot_general;optimizer/mul") == "head"
    assert scopes.scope_of("jit(step)/jit(_threefry_split)/xor") is None
    assert scopes.phase_of("transpose(jvp(layers))/checkpoint/rematted_computation/x") \
        == "recompute"
    assert scopes.phase_of("transpose(jvp(layers))/checkpoint/mamba.ssd/mul") == "bwd"
    assert scopes.phase_of("jvp(layers)/mamba.ssd/mul") == "fwd"


def test_device_time_by_scope_with_modules_from_the_modules_line():
    red = scopes.reduce(_trace([{"XLA Ops": OPS, "XLA Modules": MODULES}]),
                        scopes.op_names(HLO))
    # the while's self time (10 - 7.5 ms of body ops) and the metadata-less
    # copy inside it go to layers; the copy after it, the rng op with no
    # scope and the other program's fusion.1 are unscoped
    assert red["scopes"] == _ms({"layers": 4.5, "norm": 1, "mamba.ssd": 4.5,
                                 "optimizer": 2, "unscoped": 2.5})
    assert red["phases"]["mamba.ssd"] == _ms({"fwd": 2, "bwd": 1, "recompute": 1.5})
    assert red["phases"]["layers"] == _ms({"fwd": 4.5, "bwd": 0, "recompute": 0})
    assert red["busy_s"] == pytest.approx(14.5e-3)
    assert red["unscoped_share"] == pytest.approx(2.5 / 14.5)
    assert dict(red["unscoped_top"]) == _ms({"copy.6": 1, "fusion.1": 1, "fusion.7": 0.5})


def test_modules_from_the_op_stats_averaged_over_devices_per_step():
    ops = [("fusion.8", 1, 4), ("fusion.1", 5, 2)]
    dev = {"XLA Ops": ops}
    red = scopes.reduce(
        _trace([dev, dev], stats={"XLA Ops": ["jit_step(7)", "jit_make(9)"]}),
        scopes.op_names(HLO), steps=2)
    assert red["scopes"] == _ms({"optimizer": 2, "unscoped": 1})
    # without a module anywhere, a name only one program has is found by
    # name; fusion.1 is the step's then, being the only program compiled
    red = scopes.reduce(_trace([dev]), scopes.op_names(HLO))
    assert red["scopes"] == _ms({"optimizer": 4, "norm": 2})


def test_table_lists_every_scope_and_the_unscoped_ops():
    red = scopes.reduce(_trace([{"XLA Ops": OPS, "XLA Modules": MODULES}]),
                        scopes.op_names(HLO))
    text = scopes.table(red)
    assert re.search(r"^mamba\.ssd\s+4\.500\s+2\.000\s+1\.000\s+1\.500\s+31\.03%$", text, re.M)
    assert "unscoped share of busy time: 17.241%" in text
    assert "  unscoped fusion.7: 0.500 ms" in text


def test_a_trace_without_the_window_or_a_device_is_refused():
    names = scopes.op_names(HLO)
    host_only = ProfileData.from_text_proto(_plane(99, "/host:CPU", {"python3": WINDOW}))
    with pytest.raises(ValueError, match="device plane"):
        scopes.reduce(host_only, names)
    no_window = ProfileData.from_text_proto(
        _plane(1, "/device:TPU:0", {"XLA Ops": OPS}) + "\n"
        + _plane(99, "/host:CPU", {"python3": [("x", 0, 1)]}))
    with pytest.raises(ValueError, match="bench.window"):
        scopes.reduce(no_window, names)


# ---------------------------------------------------------------------------
# the program's steps at a test size
# ---------------------------------------------------------------------------


def _lowered_steps():
    """(train step, decode step) of the tiny ssm cells, lowered."""
    cell = tiny.train_cell("ssm")
    cfg, tr = cell["config"], cell["traffic"]
    c, cfgm = harness.model_dict(cfg), program.model_config(cfg)
    batch_fn = traffic.train_batch_fn(tr, c["vocab_size"], 5)
    step, place, sspec = program.train_setup(cfgm, batch_fn, tr["batch"], lr=1e-3, mesh="1x1")
    state = jax.eval_shape(lambda: program.init_train_state(
        cfgm, lambda k: weights.make(c, k), seed_key(5, "weights"), seed_key(5, "rng"), sspec))
    train = step.lower(state, jax.eval_shape(lambda: place(0)))

    cell = tiny.decode_cell("ssm")
    cfg, tr = cell["config"], cell["traffic"]
    c, cfgm = harness.model_dict(cfg), program.model_config(cfg)
    qp = jax.eval_shape(lambda: program.serve_weights(
        cfgm, lambda k: weights.make(c, k), seed_key(5, "weights")))
    prompts = jax.ShapeDtypeStruct((tr["batch"], tr["prompt"]), jnp.int32)
    _, cache = jax.eval_shape(program.prefill_step(cfgm, tr["mesh"], tr["cache_len"]),
                              qp, {"tokens": prompts})
    token = jax.ShapeDtypeStruct((tr["batch"],), jnp.int32)
    decode = program.decode_step(cfgm, tr["mesh"]).lower(qp, token, cache)
    return train, decode


def _instructions(text: str) -> list[str]:
    """A compiled program's instructions without their metadata."""
    keep = [ln for ln in text.splitlines()
            if re.match(r"^\s*(ROOT\s+)?%\S+\s*=\s|^(ENTRY\s+)?%\S+ .*\{$|^\s*\}$", ln)]
    return [re.sub(r", metadata=\{[^}]*\}", "", ln) for ln in keep]


@pytest.fixture(scope="module")
def steps():
    texts = []
    lowered = _lowered_steps()
    with scopes.compiled_texts(texts):
        compiled = [low.compile() for low in lowered]
    assert texts == [c.as_text() for c in compiled]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = _lowered_steps()
    return {"lowered": lowered, "compiled": compiled, "texts": texts, "bare": bare}


def _scopes(text):
    out = {}
    for path in scopes.op_names(text).values():
        if path and (sc := scopes.scope_of(path)):
            out.setdefault(sc, set()).add(scopes.phase_of(path))
    return out


MAMBA = {"embed", "layers", "norm", "mamba.in_proj", "mamba.conv", "mamba.out", "head"}


def test_the_train_step_carries_its_scopes(steps):
    got = _scopes(steps["texts"][0])
    assert MAMBA | {"mamba.ssd", "grad_stats", "optimizer"} == set(got)
    # remat "block": the SSD runs forward, recomputed and backward
    assert got["mamba.ssd"] == {"fwd", "bwd", "recompute"}
    assert got["optimizer"] == {"fwd"}


def test_the_decode_step_carries_its_scopes(steps):
    got = _scopes(steps["texts"][1])
    assert MAMBA | {"mamba.ssm_update", "serve.weights"} == set(got)


@pytest.mark.parametrize("which", [0, 1], ids=["train", "decode"])
def test_the_scopes_change_nothing_but_metadata(steps, which):
    scoped, bare = steps["lowered"][which], steps["bare"][which]
    assert scoped.as_text(debug_info=False) == bare.as_text(debug_info=False)
    assert _instructions(steps["texts"][which]) == _instructions(bare.compile().as_text())


def test_the_programs_are_compiled_under_a_key_that_holds_their_metadata():
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    with scopes.compiled_texts([]):
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert jax.config.jax_compilation_cache_include_metadata_in_key == before


@pytest.mark.parametrize("which", [0, 1], ids=["train", "decode"])
def test_no_host_callback_in_the_compiled_steps(steps, which):
    targets = re.findall(r'custom_call_target="([^"]*)"', steps["texts"][which])
    assert not [t for t in targets if "callback" in t], targets


# ---------------------------------------------------------------------------
# the traced run's record and the per-layer metrics that read it
# ---------------------------------------------------------------------------


def test_a_traced_record_carries_device_time_by_scope():
    tracer = harness.Tracer(True, "tiny")
    tracer.texts.append(HLO)
    pd = _trace([{"XLA Ops": OPS, "XLA Modules": MODULES}])
    red = tracer.summarise(pd, steps=2)
    assert red["window_s"] == pytest.approx(20e-3)  # bench.trace.reduce's own
    assert red["scopes"] == scopes.reduce(pd, scopes.op_names(HLO), steps=2)
    assert red["scopes"]["scopes"]["mamba.ssd"] == pytest.approx(4.5e-3 / 2)


@pytest.mark.parametrize("on", [True, False])
def test_only_a_traced_run_compiles_under_a_key_with_metadata(on):
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    tracer = harness.Tracer(on, "tiny")
    with tracer.programs():
        assert jax.config.jax_compilation_cache_include_metadata_in_key == (on or before)
        jax.jit(lambda x: x + 1).lower(1.0).compile()
    assert jax.config.jax_compilation_cache_include_metadata_in_key == before
    assert len(tracer.texts) == (1 if on else 0)


#: metric -> (cell kind, scope it reads)
SCOPE_METRICS = {"ssd_ms.train": ("train", "mamba.ssd"),
                 "optimizer_ms.train": ("train", "optimizer"),
                 "ssm_update_ms.decode": ("decode", "mamba.ssm_update"),
                 "weights_ms.decode": ("decode", "serve.weights"),
                 "layer_scan_ms.decode": ("decode", "layers")}


@pytest.mark.parametrize("metric", sorted(SCOPE_METRICS))
def test_a_scope_metric_reads_its_scope_in_ms_per_step(metric):
    kind, scope = SCOPE_METRICS[metric]
    times = {s: 1e-3 * (i + 1) for i, (_, s) in enumerate(SCOPE_METRICS.values())}
    record = {"kind": kind, "trace": {"scopes": {"scopes": times}}}
    assert harness.read_metric(metric, record) == pytest.approx(1e3 * times[scope])
    del times[scope]
    assert harness.read_metric(metric, record) is None
    other = "decode" if kind == "train" else "train"
    assert harness.read_metric(metric, {"kind": other, "trace": {"scopes": {"scopes": {
        scope: 1.0}}}}) is None
    assert harness.read_metric(metric, {"kind": kind, "trace": None}) is None
