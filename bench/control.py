#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and the
faults', at the cell's own size.  Not part of the benchmark's runs.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 10]

Training cells, per seed: the reference trained in float32 is what the
program is held to; the control is that reference computed one precision
step lower (``bench.ref.common.CONTROL``), and the fault "half of the batch
left out, the mean taken over the rest" is the reference trained on the
first half of each batch.  Each takes the program's place: its numbers
against the float32 reference are judged by the cell's limits with the
same ``harness.judge`` that decides a run's ``correct``, which has to come
out false.  (A state left unchanged reads 1 by ``change_gap``'s measure
and needs no run.)

Decode cells, per seed: one whole run of the cell, its ``logit_gap`` and
the control's gap over the same prompts and served tokens, all in one
process, both judged by the cell's limits.

Prints one JSON line per seed, then a summary line.
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


def train_readings(cell: dict, seed: int) -> dict:
    from bench import check, harness, traffic
    from bench.ref.common import CONTROL
    from bench.weights import seed_key

    c, tr, opt = harness.model_dict(cell["config"]), cell["traffic"], cell["config"]["optimizer"]
    wkey = seed_key(seed, "weights")
    batch_fn = traffic.train_batch_fn(tr, c["vocab_size"], seed)
    args = (c, opt, wkey, batch_fn, tr["check_steps"], tr["ref_rows"])
    want = check.train_reference(*args)
    out = {}
    for name, kw in (("control", {"num": CONTROL}), ("half_batch", {"half": True})):
        nums, _ = check.train_numbers(check.train_reference(*args, **kw), want)
        correct, checks = harness.judge(nums, cell["limits"])
        out[name] = {"correct": correct, "checks": checks}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from bench import program

    program.configure_compile_cache()
    from bench import harness

    cell = harness.load_cell(args.workload)
    harness.device_check(cell["chips"], require_tpu=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell["traffic"]["kind"] == "train":
            r = train_readings(cell, seed)
        else:
            res = harness.run(cell, seed, args.seconds, False, t0, control=True)
            r = {"program": {"correct": res["correct"], "checks": res["checks"]},
                 "control": res["control"],
                 "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                 "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        r["seed"] = seed
        r["seconds"] = time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps({"workload": args.workload, "readings": rows}), flush=True)


if __name__ == "__main__":
    main()
