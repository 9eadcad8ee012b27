"""Cells at sizes a CPU test can hold, in the shapes of the real ones."""

from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _cfg(name: str, **sizes) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(sizes)
    return cfg


SSM = dict(num_layers=2, d_model=64, vocab_size=256, ssm_state=16, ssm_head_dim=16)


def _hybrid_cfg() -> dict:
    """The repository's hybrid family (sliding-window GQA beside a Mamba-2
    branch, then a SwiGLU MLP) at a test size: no cell runs it yet, so it
    has no configuration file; the harness and the reference carry it."""
    cfg = _cfg("mamba2_780m", num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
               vocab_size=256, head_dim=16, ssm_state=8, ssm_head_dim=16, sliding_window=16,
               attn_chunk_kv=16, rope_theta=10000.0, tie_embeddings=False)
    del cfg["ssm_expand"]
    cfg.update(name="tiny_hybrid", family="hybrid", reference="bench/ref/hybrid.py")
    return cfg


def _model(family: str) -> dict:
    return _hybrid_cfg() if family == "hybrid" else _cfg("mamba2_780m", **SSM)
UNITS = {"train_tokens_per_s": "tokens/s", "decode_tokens_per_s": "tokens/s",
         "token_gap_ms_p95": "ms", "setup_s": "s", "mfu.train": "%", "mfu.decode": "%",
         "idle_share.train": "%", "idle_share.decode": "%"}


TRAIN_LIMITS = {"hybrid": {"loss_gap": 1e-3, "grad_norm_gap": 0.05, "change_gap": 0.05},
                "ssm": {"loss_gap": 5e-4, "grad_norm_gap": 0.008, "change_gap": 0.013}}


def train_cell(family: str = "ssm", limits=None) -> dict:
    return {
        "name": f"tiny.train.{family}", "chips": 1, "config": _model(family),
        "traffic": {"kind": "train", "batch": 4, "seq": 32, "mesh": "1x1",
                    "check_steps": 3, "ref_rows": 2},
        "limits": limits or TRAIN_LIMITS[family],
        "end_to_end": ["train_tokens_per_s", "setup_s"],
        "per_layer": ["mfu.train", "idle_share.train"], "units": UNITS,
    }


def decode_cell(family: str = "hybrid", limits=None) -> dict:
    cfg = _model(family)
    if family == "hybrid":
        tr = {"kind": "decode", "batch": 4, "prompt": 40, "cache_len": 48, "prefill_rows": 2,
              "replay_at": 48, "check_sessions": 2, "mesh": "1x1"}
    else:
        tr = {"kind": "decode", "batch": 4, "prompt": 32, "cache_len": None, "prefill_rows": 2,
              "replay_at": None, "check_sessions": 2, "mesh": "1x1"}
    return {
        "name": f"tiny.decode.{family}", "chips": 1, "config": cfg, "traffic": tr,
        "limits": limits or {"logit_gap": 0.1},
        "end_to_end": ["decode_tokens_per_s", "token_gap_ms_p95", "setup_s"],
        "per_layer": ["mfu.decode", "idle_share.decode"], "units": UNITS,
    }


def with_limits(cell: dict, **limits) -> dict:
    cell = copy.deepcopy(cell)
    cell["limits"].update(limits)
    return cell
