"""One run of one cell: set-up, the measured window, the traced reduction and
the comparison with the reference.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its configuration
is the JSON file that entry's config names, its traffic mix
``bench/traffic/<traffic>.json``, its limits ``bench/cells/<cell>.json``,
and each per-layer metric a reader ``bench/metrics/<metric>.py`` with a
function ``read(record) -> float | None``.  Nothing here names a cell.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, program, traffic, weights, work
from bench.peaks import PEAKS, peaks_for
from bench.weights import seed_key

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# what a cell is
# ---------------------------------------------------------------------------


def load_cell(name: str, benchmark: dict | None = None) -> dict:
    bm = benchmark or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    applies = lambda m: "workloads" not in m or name in m["workloads"]
    return {
        "name": name,
        "chips": w["chips"],
        "config": json.loads((ROOT / entry["file"]).read_text()),
        "traffic": traffic.load(w["traffic"]),
        "limits": json.loads((BENCH / "cells" / f"{name}.json").read_text())["limits"],
        "end_to_end": [m["name"] for m in bm["end_to_end"] if applies(m)],
        "per_layer": [m["name"] for m in bm["per_layer"] if applies(m)],
        "units": {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]},
    }


def model_dict(cfg: dict) -> dict:
    """The configuration's model sizes, as ``work`` and the reference read
    them: those the program's ``ModelConfig`` names, JSON lists as tuples."""
    return {k: program.hashable(cfg[k]) for k in program.model_fields() if k in cfg}


def read_metric(name: str, record: dict):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def device_check(chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} device_kind={d.device_kind} device_count={len(devs)} "
        f"jax={jax.__version__}")
    if require_tpu and d.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {d.platform!r}); "
                         "the benchmark has no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    peaks = peaks_for(d.device_kind) if require_tpu else PEAKS["TPU v5 lite"]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "devices": devs[:chips], "peaks": peaks}


def peak_bytes(devs) -> int:
    stats = [d.memory_stats() or {} for d in devs]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class CompileCounter:
    """Counts lowerings (each jit cache miss lowers) while ``on``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _inst = None

    def __init__(self):
        self.on, self.n = False, 0

    @classmethod
    def get(cls):
        if cls._inst is None:
            cls._inst = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._inst._hear)
        return cls._inst

    def _hear(self, name, secs, **kw):
        if self.on and name == self.EVENT:
            self.n += 1


def _memory_line(what: str, compiled) -> None:
    ma = compiled.memory_analysis()
    log(f"memory_analysis {what}: " + " ".join(
        f"{k}={int(getattr(ma, k))}" for k in ("argument_size_in_bytes", "output_size_in_bytes",
                                              "alias_size_in_bytes", "temp_size_in_bytes")))


class Tracer:
    """The profiler around the window when ``--trace 1``.

    Such a run also compiles its programs inside
    ``bench.scopes.compiled_texts``, from set-up to the window's close
    (:meth:`programs`): under a cache key that holds their op names, so
    that the names in the trace are the program's own, and keeping their
    text for the reduction by scope.  An untraced run compiles, and loads
    from the cache, exactly as it would without this class.  ``keep``, a
    directory, also gets the trace and the programs' text."""

    def __init__(self, on: bool, cell: str, keep: str | None = None):
        self.on = on
        self.dir = OUT / "trace" / cell
        self.keep = keep
        self.texts: list[str] = []
        self._compiling = contextlib.ExitStack()

    def programs(self) -> contextlib.ExitStack:
        """The context of a run's set-up and window (closed with the window
        when the window closes first)."""
        if self.on:
            from bench import scopes

            self._compiling.enter_context(scopes.compiled_texts(self.texts))
        return self._compiling

    def __enter__(self):
        if self.on:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
        return self

    def __exit__(self, *exc):
        if self.on:
            jax.profiler.stop_trace()
            self._compiling.close()

    def reduce(self, steps: int) -> dict | None:
        if not self.on:
            return None
        from bench import trace as tr

        path = tr.find_xplane(str(self.dir))
        if self.keep:
            out = pathlib.Path(self.keep)
            out.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, out / "window.xplane.pb")
            (out / "programs.json").write_text(json.dumps(self.texts))
        red = self.summarise(tr.load(path), steps)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red

    def summarise(self, pd, steps: int) -> dict:
        """``bench.trace.reduce`` of the window, with device time per step by
        the program's scopes under ``scopes`` (``bench.scopes.reduce``)."""
        from bench import scopes
        from bench import trace as tr

        red = tr.reduce(pd, steps=steps)
        red["scopes"] = scopes.reduce(pd, scopes.program_names(self.texts), steps=steps)
        log(f"device time per step by scope over {steps} steps:\n{scopes.table(red['scopes'])}")
        return red


TA = jax.profiler.TraceAnnotation


class GcWatch:
    """Around the window: collects once and freezes what set-up left on the
    heap, so a collection in the window scans only what the window made,
    and logs every collection the window saw with its pause."""

    def __enter__(self):
        gc.collect()
        gc.freeze()
        self.pauses, self._t = [], None
        gc.callbacks.append(self._hear)
        return self

    def _hear(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def __exit__(self, *exc):
        gc.callbacks.remove(self._hear)
        gc.unfreeze()
        longest = max(self.pauses, key=lambda p: p[1], default=(None, 0.0))
        log(f"gc in the window: {len(self.pauses)} collections, by generation "
            f"{[sum(g == k for g, _ in self.pauses) for k in range(3)]}, longest "
            f"{longest[1] * 1e3:.2f} ms (generation {longest[0]})")


class PhaseClock:
    """Logs the seconds each set-up phase took."""

    def __init__(self):
        self.t = time.perf_counter()

    def __call__(self, what: str) -> None:
        now = time.perf_counter()
        log(f"set-up: {what} in {now - self.t:.2f}s")
        self.t = now


# ---------------------------------------------------------------------------
# training cells
# ---------------------------------------------------------------------------


def run_train(c, cfg, tr, seed, seconds, tracer, counter, dev, mark_open, control):
    phase = PhaseClock()
    opt = cfg["optimizer"]
    cfgm = program.model_config(cfg)
    program.check_optimizer(opt)
    wkey = seed_key(seed, "weights")
    program.check_layout(cfgm, jax.eval_shape(lambda: weights.make(c, wkey)))
    batch_fn = traffic.train_batch_fn(tr, c["vocab_size"], seed)
    step_fn, place, sspec = program.train_setup(cfgm, batch_fn, tr["batch"], lr=opt["lr"],
                                                mesh=tr["mesh"])
    state = program.init_train_state(cfgm, lambda k: weights.make(c, k), wkey,
                                     seed_key(seed, "rng"), sspec)
    jax.block_until_ready(state)
    phase("weights and optimizer state")
    step = step_fn.lower(state, place(0)).compile()
    phase("train step compiled (or loaded from the cache)")
    _memory_line("train step", step)

    # the first steps, through the window's own call and feed, are compared
    # with the reference after the window
    got = {"loss": []}
    n_check = tr["check_steps"]
    for s in range(n_check):
        state, m = step(state, place(s))
        got["loss"].append(float(m["ce"]))
        if s == 0:
            got["grad_norm"] = {n: v / (1 - opt["b1"])
                                for n, v in program.first_moment_norms(state).items()}
    got["change"] = {n: float(v) for n, v in jax.jit(lambda p, k: {
        n: jnp.sqrt(jnp.sum(jnp.square(a - weights.make(c, k, names={n})[n])))
        for n, a in program.from_tree(p).items()})(state.params, wkey).items()}
    phase(f"{n_check} checked steps")
    log("set-up steps: ce " + " ".join(f"{v:.6f}" for v in got["loss"]))

    tokens_per_step = tr["batch"] * tr["seq"]
    s, steps, failed, pending = n_check, 0, 0, None
    with tracer, GcWatch():
        t_open = mark_open()
        counter.on = True
        with TA("bench.window"):
            while True:
                with TA("bench.dispatch"):
                    state, m = step(state, place(s))
                s += 1
                if pending is not None:
                    with TA("bench.fetch_loss"):
                        failed += not math.isfinite(float(pending["ce"]))
                    steps += 1
                    if time.perf_counter() - t_open >= seconds:
                        break
                pending = m
            with TA("bench.fetch_loss"):
                failed += not math.isfinite(float(m["ce"]))
            steps += 1
            t_close = time.perf_counter()
        counter.on = False
    elapsed = t_close - t_open
    peak = peak_bytes(dev["devices"])
    del state, m, pending, step
    gc.collect()

    flops_tok = work.train_flops_per_token(c, tr["seq"])
    e2e = {"train_tokens_per_s": steps * tokens_per_step / elapsed}
    record = {"kind": "train", "c": c, "wire": cfg["wire"], "traffic": tr, "peaks": dev["peaks"],
              "chips": dev["count"], "window_s": elapsed, "steps": steps,
              "tokens": steps * tokens_per_step, "flops_per_token": flops_tok,
              "trace": tracer.reduce(steps)}

    t_ref = time.perf_counter()
    want = check.train_reference(c, opt, wkey, batch_fn, n_check, tr["ref_rows"])
    log(f"reference: {n_check} steps in {time.perf_counter() - t_ref:.1f}s")
    nums, left_out = check.train_numbers(got, want)
    log(f"reference ce {want['loss']}; left out of change_gap: {left_out}")
    return e2e, record, nums, steps, failed, peak


# ---------------------------------------------------------------------------
# decode cells
# ---------------------------------------------------------------------------


def run_decode(c, cfg, tr, seed, seconds, tracer, counter, dev, mark_open, control):
    phase = PhaseClock()
    cfgm = program.model_config(cfg)
    wkey = seed_key(seed, "weights")
    program.check_layout(cfgm, jax.eval_shape(lambda: weights.make(c, wkey)))
    B, P, rows, replay_at = tr["batch"], tr["prompt"], tr["prefill_rows"], tr["replay_at"]
    qp = program.serve_weights(cfgm, lambda k: weights.make(c, k), wkey)
    prompts = traffic.prompts(tr, c["vocab_size"], seed)
    jax.block_until_ready((qp, prompts))
    phase("weights and prompts")

    prefill = program.prefill_step(cfgm, tr["mesh"], tr["cache_len"]).lower(
        qp, {"tokens": prompts[:rows]}).compile()
    phase("prefill step compiled (or loaded from the cache)")
    _memory_line("prefill step", prefill)
    first = jax.jit(lambda lg: jnp.argmax(lg, axis=-1).astype(jnp.int32))
    caches, toks = [], []
    for g in range(0, B, rows):
        last, cache_g = prefill(qp, {"tokens": prompts[g:g + rows]})
        toks.append(first(last))
        caches.append(cache_g)
    del prefill, last, cache_g
    cache = program.stack_caches(caches) if len(caches) > 1 else caches[0]
    del caches
    tok0 = jnp.concatenate(toks) if len(toks) > 1 else toks[0]
    jax.block_until_ready(cache)
    phase(f"{B} prompts of {P} prefilled, {rows} at a time")
    step = program.decode_step(cfgm, tr["mesh"]).lower(qp, tok0, cache).compile()
    phase("decode step compiled (or loaded from the cache)")
    _memory_line("decode step", step)
    replay = snap = None
    if replay_at:
        snap = jax.jit(lambda a, b: (jnp.copy(a), jnp.copy(b)))(cache.conv, cache.ssm)
        replay = program.replay_fn(P).lower(cache, *snap).compile()
        cache = replay(cache, *snap)  # at the prompt's end already: changes nothing

    served = [np.asarray(tok0)]  # served[k]: token at position P + k (first pass)
    tok, in_pos, first_pass = tok0, P, True

    def advance(tok, cache, in_pos, first_pass):
        nxt, cache = step(qp, tok, cache)
        in_pos += 1
        if replay_at and in_pos == replay_at:
            with TA("bench.replay"):
                cache = replay(cache, *snap)
            return nxt, tok0, cache, P, False
        return nxt, nxt, cache, in_pos, first_pass

    for _ in range(2):  # warm-up: these tokens are served too
        fp_out = first_pass
        nxt, tok, cache, in_pos, first_pass = advance(tok, cache, in_pos, first_pass)
        if fp_out:
            served.append(np.asarray(nxt))
    phase("two warm-up steps")

    def fetch(pending):
        h = np.asarray(pending[0])
        arrivals.append(time.perf_counter())
        if pending[1]:
            served.append(h)
        return int(((h < 0) | (h >= c["vocab_size"])).any())

    arrivals, steps, failed, pending, live_sum = [], 0, 0, None, 0
    with tracer, GcWatch():
        t_open = mark_open()
        counter.on = True
        with TA("bench.window"):
            while True:
                live_sum += in_pos + 1
                fp_out = first_pass
                with TA("bench.dispatch"):
                    nxt, tok, cache, in_pos, first_pass = advance(tok, cache, in_pos, first_pass)
                prev, pending = pending, (nxt, fp_out)
                if prev is not None:
                    with TA("bench.fetch_tokens"):
                        failed += fetch(prev)
                    steps += 1
                    if arrivals[-1] - t_open >= seconds:
                        break
            with TA("bench.fetch_tokens"):
                failed += fetch(pending)
            steps += 1
        counter.on = False
    elapsed = arrivals[-1] - t_open
    gaps_ms = np.diff(np.asarray(arrivals)) * 1e3
    peak = peak_bytes(dev["devices"])
    conv_b, ssm_b = cache.conv.dtype.itemsize, cache.ssm.dtype.itemsize
    del qp, cache, tok, nxt, tok0, step, replay, snap, pending, prev
    gc.collect()

    mean_live = live_sum / steps
    e2e = {"decode_tokens_per_s": steps * B / elapsed,
           "token_gap_ms_p95": float(np.percentile(gaps_ms, 95))}
    log(f"window: {steps} steps, token gap ms median {np.median(gaps_ms):.3f} "
        f"p95 {e2e['token_gap_ms_p95']:.3f} max {gaps_ms.max():.3f} "
        f"(after {int(gaps_ms.argmax()) + 1} steps)")
    record = {"kind": "decode", "c": c, "wire": cfg["wire"], "traffic": tr, "peaks": dev["peaks"],
              "chips": dev["count"], "window_s": elapsed, "steps": steps,
              "tokens": steps * B,
              "flops_per_step": work.decode_flops_per_step(c, B, mean_live),
              "bytes_per_step": work.decode_bytes_per_step(c, cfg["wire"], B, mean_live,
                                                           conv_b, ssm_b),
              "trace": tracer.reduce(steps)}

    sess = traffic.check_sessions(tr, seed)
    toks_served = np.stack(served, axis=1)  # [B, n]
    seqs = np.concatenate([np.asarray(prompts)[sess], toks_served[sess]], axis=1)
    t_ref = time.perf_counter()
    nums = check.decode_gaps(c, wkey, seqs, P, control=control)
    log(f"reference: {len(sess)} sessions in {time.perf_counter() - t_ref:.1f}s")
    log(f"checked sessions {sess}: {toks_served.shape[1]} served tokens each")
    return e2e, record, nums, steps, failed, peak


RUNNERS = {"train": run_train, "decode": run_decode}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def judge(nums: dict, limits: dict, failed: int = 0) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    correct = (failed == 0
               and all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
                       for v in checks.values()))
    return bool(correct), checks


def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, control: bool = False) -> dict:
    """Run ``cell`` once; returns the result object (the last line).
    ``control`` (decode cells, ``bench/control.py``) also reads the
    control's numbers over the same prompts and served tokens, judges them
    by the cell's limits as the program's are judged, and returns that
    under ``control``."""
    dev = device_check(cell["chips"], require_tpu)
    counter = CompileCounter.get()
    tracer = Tracer(trace, cell["name"])
    cfg, tr = cell["config"], cell["traffic"]
    c = model_dict(cfg)
    opened = {}

    def mark_open():
        opened["t"] = time.perf_counter()
        return opened["t"]

    with tracer.programs():
        e2e, record, nums, steps, failed, peak = RUNNERS[tr["kind"]](
            c, cfg, tr, seed, seconds, tracer, counter, dev, mark_open, control)
    e2e["setup_s"] = opened["t"] - t_start
    log(f"compilations inside the window: {counter.n}; peak_bytes_in_use={peak}")

    correct, checks = judge(nums, cell["limits"], failed)
    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": peak}
    if trace:
        red = record["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for name in cell["per_layer"]:
            v = read_metric(name, record)
            if v is not None:
                metrics[name] = {"value": v, "unit": cell["units"][name]}
    else:
        metrics = {k: {"value": e2e[k], "unit": cell["units"][k]} for k in cell["end_to_end"]}
    out = {"correct": correct, "attempted": steps, "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    if control:
        ctl_correct, ctl_checks = judge(nums["control"], cell["limits"])
        out["control"] = {"correct": ctl_correct, "checks": ctl_checks}
    out["checks"] = checks
    return out
