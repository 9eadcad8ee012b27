#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (compile cache, device check, weights and inputs made on the device
from ``--seed``, compilation and warm-up of the cell's own shapes), then a
window of ``--seconds`` that closes at the first step boundary after it,
then the comparison with the plain reference.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The numbers
compared with the reference and their limits are the last lines on
standard error; the result object is the last line on standard output.
Without a TPU, or with fewer chips than the cell needs, it exits non-zero
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_out" / "tpu_logs"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import program

    log_cache = program.configure_compile_cache()
    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.log(f"cell {args.workload} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}; compile cache {log_cache}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
