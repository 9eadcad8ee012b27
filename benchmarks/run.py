"""Benchmark aggregator: one function per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--smoke] [--json]

``--smoke`` runs every bench at its CI size (reduced kernel shapes, the
150-matrix figure2 corpus, the small-payload collectives subprocess, the
analytic-only roofline) and validates the JSON artifact; ``--json`` makes
the kernel bench emit ``BENCH_kernels.json`` at the repo root (the
persistent perf-trajectory record; smoke runs divert to the gitignored
``benchmarks/results/BENCH_kernels.smoke.json`` so they never clobber the
committed full-size baseline) and then *folds* the other benches' summaries
(``benchmarks/results/{figure2,isa_tables,collectives,roofline}.json``)
into it, so one artifact carries the whole trajectory.  Benches whose
subsystem is still a stub (NotImplementedError) are reported as SKIP, not
failures.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

RESULTS = os.path.join(os.path.dirname(__file__), "results")

# artifact key -> (bench module name, results file it writes)
FOLD_SOURCES = {
    "figure2": ("figure2", "figure2.json"),
    "isa": ("tables_isa", "isa_tables.json"),
    "collectives": ("collectives", "collectives.json"),
    "roofline": ("roofline", "roofline.json"),
}


def _fold_results(smoke: bool, fold_keys: set) -> None:
    """Attach summaries of the benches that ran *this invocation* to the
    artifact — never stale results/ files from earlier runs (a leftover
    smoke-sized figure2.json must not masquerade as full-baseline data)."""
    from benchmarks.kernel_bench import bench_json_path

    path = bench_json_path(smoke)
    with open(path) as fh:
        report = json.load(fh)
    for key in fold_keys:
        src = os.path.join(RESULTS, FOLD_SOURCES[key][1])
        if os.path.exists(src):
            with open(src) as fh:
                report[key] = json.load(fh)
    with open(path, "w") as fh:
        json.dump(report, fh, indent=2)
        fh.write("\n")


def _check_format_dispatch(report: dict) -> None:
    """Fail if a wire format registered in core is unreachable from the
    kernels.ops dispatch layer or missing from the bench format matrix."""
    import jax.numpy as jnp

    from repro.core.formats import kernel_wire_names, wire_format
    from repro.kernels import ops

    registered = set(kernel_wire_names())
    dispatchable = set(ops.supported_wire_formats())
    unreachable = registered - dispatchable
    assert not unreachable, (
        f"formats registered in core.formats but unreachable from "
        f"kernels.ops dispatch: {sorted(unreachable)}"
    )
    bench_fmts = {r["fmt"] for r in report["decode"]}
    missing = registered - bench_fmts
    assert not missing, (
        f"registered formats missing from the bench decode matrix: {sorted(missing)}"
    )
    # every registered format must also have encode rows (the encode path is
    # the expensive codec direction — it cannot silently drop off the bench)
    enc_fmts = {r["fmt"] for r in report["encode"]}
    missing_enc = registered - enc_fmts
    assert not missing_enc, (
        f"registered formats missing from the bench encode matrix: {sorted(missing_enc)}"
    )
    # probe the real dispatch path (kernel or ref, per backend) per format;
    # block-scaled formats are probed through their interleaved payload
    # shape (an all-zero payload has scale byte 0 -> clamped 2^-126 scale
    # and zero elements, decoding to exact zeros)
    for name in sorted(registered):
        wf = wire_format(name)
        cols = 128 * 33 // 32 if wf.is_block_scaled else 128
        out = ops.decode(jnp.zeros((8, cols), wf.storage), name)
        assert out.shape == (8, 128) and float(jnp.max(jnp.abs(out))) == 0.0, name
    print(f"bench_format_dispatch,0,{len(registered)} formats reachable "
          f"({','.join(sorted(registered))})")


def _validate_bench_json(smoke: bool, fold_keys: set) -> None:
    from benchmarks.kernel_bench import bench_json_path

    with open(bench_json_path(smoke)) as fh:
        report = json.load(fh)
    required = {"schema", "decode", "encode", "encode_fused", "matmul",
                "attention", "train_step", "decode_speedup_lut_vs_bits",
                "encode_speedup_lut_vs_bits", "encode_fused_speedup",
                "hbm_model_bytes_1024x1024",
                "format_matrix_decode_melem_s", "takum_vs_zoo", "takum_vs_mx",
                } | fold_keys
    missing = required - report.keys()
    assert not missing, f"BENCH_kernels.json missing keys: {sorted(missing)}"
    assert report["schema"] == "bench_kernels/v6", report["schema"]
    # v6: every throughput row carries interleaved-rep bootstrap stats
    for section in ("decode", "encode", "encode_fused", "matmul",
                    "attention", "train_step"):
        for r in report[section]:
            st = r.get("stats")
            assert st is not None, f"{section} row missing stats: {r}"
            assert {"median", "ci_lo", "ci_hi", "reps"} <= st.keys(), st
            assert st["reps"] >= 3, f"{section} row has too few reps: {st}"
            assert st["ci_lo"] <= st["median"] <= st["ci_hi"], st
    impls = {(r["fmt"], r["impl"]) for r in report["decode"]}
    assert {("t8", "bits"), ("t8", "lut"), ("t16", "bits"), ("t16", "lut"),
            ("e4m3", "lut"), ("e5m2", "lut"), ("bf16", "bits"),
            ("mxe4m3", "lut"), ("mxe4m3", "bits"), ("mxe5m2", "lut"),
            ("mxt8", "lut"), ("mxt8", "bits")} <= impls, impls
    enc_impls = {(r["fmt"], r["impl"]) for r in report["encode"]}
    assert {("t8", "lut"), ("t16", "lut"), ("t16", "bits"), ("e4m3", "bits"),
            ("e5m2", "bits"), ("bf16", "bits"), ("mxe4m3", "bits"),
            ("mxe5m2", "bits"), ("mxt8", "bits"),
            ("mxt8", "lut")} <= enc_impls, enc_impls
    fused = {(r["fmt"], r["path"]) for r in report["encode_fused"]}
    assert {("t8", "fused"), ("t8", "separate"), ("t16", "fused"),
            ("t16", "separate"), ("mxe4m3", "fused"), ("mxe4m3", "separate"),
            ("mxt8", "fused"), ("mxt8", "separate")} <= fused, fused
    assert any(not r["aligned"] for r in report["matmul"]), "need non-aligned matmul shapes"
    mx_mm = {r["fmt"] for r in report["matmul"]}
    assert {"mxe4m3", "mxe5m2", "mxt8"} <= mx_mm, mx_mm
    mx_attn = {r["fmt"] for r in report["attention"]}
    assert {"mxe4m3", "mxe5m2", "mxt8"} <= mx_attn, mx_attn
    if "collectives" in fold_keys:
        red = report["collectives"]["wire_reduction_vs_f32"]
        assert red["t8"] == 4.0 and red["t16"] == 2.0, red
        assert red["e4m3"] == 4.0 and red["e5m2"] == 4.0 and red["bf16"] == 2.0, red
        # the block containers pay the honest scale-byte tax: 32/8.25
        assert abs(red["mxe4m3"] - 32 / 8.25) < 1e-9, red
        assert abs(red["mxt8"] - 32 / 8.25) < 1e-9, red
        assert set(report["collectives"]["pipe_hop"]) >= {
            "t8", "e4m3", "mxe4m3"
        }, "collectives summary missing compressed pipeline-hop rows"
    assert any(r["op"] == "decode_attention" for r in report["attention"])
    assert any(r["op"] == "train_step" for r in report["train_step"])
    assert any(
        r.get("policy") == "mxfp8" for r in report["train_step"]
    ), "missing the mxfp8 e2e train-step row"
    _check_format_dispatch(report)
    print(f"bench_json_valid,0,{len(report['decode'])}+{len(report['matmul'])} rows "
          f"+ folds {sorted(fold_keys)}")


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    quick = "--quick" in sys.argv
    smoke = "--smoke" in sys.argv
    emit_json = "--json" in sys.argv

    from benchmarks import (
        collectives_bench,
        figure1_dynamic_range,
        figure2_matrix_errors,
        kernel_bench,
        roofline,
        tables_isa,
    )

    if smoke:
        modules = [
            ("tables_isa", tables_isa),
            ("figure2", figure2_matrix_errors),
            ("kernels", kernel_bench),
            ("collectives", collectives_bench),
            ("roofline", roofline),
        ]
    else:
        modules = [
            ("figure1", figure1_dynamic_range),
            ("tables_isa", tables_isa),
            ("kernels", kernel_bench),
            ("collectives", collectives_bench),
            ("roofline", roofline),
        ]
        if not quick:
            modules.insert(1, ("figure2", figure2_matrix_errors))

    failures = 0
    ran = set()
    for name, mod in modules:
        argv = ["bench"] + (["--smoke"] if smoke else []) + (["--json"] if emit_json else [])
        try:
            old_argv, sys.argv = sys.argv, argv
            try:
                mod.main()
            finally:
                sys.argv = old_argv
            ran.add(name)
        except NotImplementedError as e:
            # subsystem is a declared stub (e.g. repro.dist collectives)
            print(f"{name},0,SKIP ({e})")
        except Exception:
            failures += 1
            print(f"{name},0,ERROR")
            traceback.print_exc()

    if emit_json:
        # fold/require only what ran this invocation (e.g. --quick skips
        # figure2; a stub SKIP drops its key rather than failing validation)
        fold_keys = {k for k, (mod_name, _) in FOLD_SOURCES.items() if mod_name in ran}
        try:
            _fold_results(smoke, fold_keys)
            _validate_bench_json(smoke, fold_keys)
        except Exception:
            failures += 1
            print("bench_json,0,ERROR")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
