"""Model families found by name in files of their own.

The shipped families keep the parent's numbers to the bit; a family built
at test time (``meta_family``: a window per layer with global layers, K/V
shared between layers, a prefix of meta tokens, an SSM of twice the model
width and a K/V stack over the storing layers alone) is counted by the
conventions of ``bench/work.py`` and runs through the harness with no
shipped file changed.

Limits of the test family's tiny cells, set as the cells' own were,
between the readings of the stand-in program (float32, so only its t16
Adam moments and t16 serving weights depart from the reference) over
seeds 11-15 and the least of the faults' over seeds 11-13.  Train: loss
1.7e-7 to 2.7e-7, grad norm 4.0e-5 to 1.3e-4, change 6.3e-6 to 3.2e-5;
with the K/V sharing left out loss 3.2e-3 to 1.2e-2, grad norm 0.096 to
0.16, change 0.022 to 0.047; with the prefix left out loss 7.2e-3 to
2.4e-2, grad norm 0.19 to 0.27, change 0.24 to 0.25.  Decode: logit gap 0
on every seed (the served tokens are the reference's best); 0.80 to 1.96
with the sharing left out, 0.126 to 0.93 with the prefix left out.

Run: ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""

import hashlib
import json
import time

import jax
import numpy as np
import pytest

import meta_family
import tiny
from bench import harness, program, weights, work
from bench.ref import model as ref

SEED = 2**33 + 5
MAMBA = json.loads((tiny.ROOT / "bench" / "configs" / "mamba2_780m.json").read_text())
WIRE = {"weights": "t16", "kv_cache": "t8"}


# ---------------------------------------------------------------------------
# the shipped families keep the parent's numbers
# ---------------------------------------------------------------------------


def test_mamba2_780m_counts_are_the_parents():
    c = harness.model_dict(MAMBA)
    assert work.train_flops_per_token(c, 2048) == 4961673216.0
    assert work.param_count(c) == {"embed": 77230080, "stacked": 702917376, "head": 0,
                                   "final": 1536}
    assert weights.n_params(c) == 780148992
    # the decode cell: 64 sessions, any live length (no attention), conv
    # state stored in 2 or 4 bytes, the SSM state in 4
    for live in (2049, 2100.5, 2700):
        assert work.decode_flops_per_step(c, 64, live) == 105849028608.0
        assert work.decode_bytes_per_step(c, MAMBA["wire"], 64, live, 2, 4) == 11359532544.0
        assert work.decode_bytes_per_step(c, MAMBA["wire"], 64, live, 4, 4) == 11482215936.0


#: sha256 over the sorted names and bytes of the seeded tiny weights, as
#: the parent's ``bench/weights.py`` drew them
PARENT_WEIGHTS = {
    "ssm": "91a6733f7201f8bf3cbf0bb234e56ac28b38dd5b1fb67fa6ffecc8c9b3b5833e",
    "hybrid": "0e5a29b13488faf328663fce337f7d093dbd5a0bf7d22dbb272fc5960913c085",
}


@pytest.mark.parametrize("family", ["ssm", "hybrid"])
def test_seeded_weights_are_the_parents(family):
    c = harness.model_dict(tiny.train_cell(family)["config"])
    w = jax.jit(lambda k: weights.make(c, k))(weights.seed_key(SEED, "weights"))
    h = hashlib.sha256()
    for n in sorted(w):
        h.update(n.encode())
        h.update(np.asarray(w[n]).tobytes())
    assert h.hexdigest() == PARENT_WEIGHTS[family]


@pytest.mark.parametrize("family", ["ssm", "hybrid"])
def test_the_program_tree_follows_the_programs_structure(family):
    cfg = tiny.train_cell(family)["config"]
    c, cfgm = harness.model_dict(cfg), program.model_config(cfg)
    flat = jax.eval_shape(lambda: weights.make(c, weights.seed_key(1, "weights")))
    program.check_layout(cfgm, flat)
    tree = program.to_tree(cfgm, flat)
    assert jax.tree.structure(tree) == jax.tree.structure(program.dstep.param_shapes(cfgm))
    assert program.from_tree(tree) == flat
    with pytest.raises(ValueError, match="the benchmark alone has"):
        program.check_layout(cfgm, {**flat, "layers.extra": flat["final_norm"]})


# ---------------------------------------------------------------------------
# a family built at test time
# ---------------------------------------------------------------------------


@pytest.fixture
def meta(monkeypatch):
    meta_family.install(monkeypatch)
    return monkeypatch


#: one layer of each kind: 0 global and storing K/V, 1 reading layer 0's
#: K/V through a window of 2, 2 storing and windowed; d 4, 2 query heads and
#: 1 KV head of 2, d_ff 8, vocab 10, SSM inner width 8 (2 heads of 4),
#: state 2, conv width 2, a prefix of 2
COUNTED = dict(family=meta_family.FAMILY, num_layers=3, d_model=4, num_heads=2, num_kv_heads=1,
               head_dim=2, d_ff=8, vocab_size=10, ssm_state=2, ssm_head_dim=4, ssm_expand=2,
               ssm_conv_width=2, tie_embeddings=False, layer_windows=(0, 2, 2),
               kv_from=(0, 0, 2), meta_tokens=2)


def test_the_test_family_counts(meta):
    c = COUNTED
    # attention 16 + 16 + 16 (a reading layer 32: no wk, wv); SwiGLU 96;
    # SSM in_proj 4 * (16 + 4 + 2), out_proj 32, conv 2 * 12
    assert [work.layer_matmul_params(c, i) for i in range(3)] == [288, 272, 288]
    assert work.matmul_params(c) == 848 + 40
    # per trained token of 8, with the prefix's 2 positions: 6 * (848 *
    # 10/8 + 40); attention 3 * 16 * (mean keys + 2 + 3/8), mean keys 4.5
    # global and 15/8 in a window of 2; SSM 15 * 3 * 16 * 10/8
    assert work.train_flops_per_token(c, 8) == pytest.approx(
        6600 + 48 * (6.875 + 4.25 + 4.25) + 900)
    # per sequence with 6 positions: 2 * 888, attention 16 * (8 + 4 + 4)
    # keys, SSM 5 * 48; for 2 sequences
    assert work.decode_flops_per_step(c, 2, 6) == pytest.approx(2 * (1776 + 256 + 240))
    # per layer 306 (norms 8, wq and wo 32, MLP 96, SSM 170) on 3 layers,
    # wk and wv 32 on the 2 storing layers; the prefix's 8 only in prefill
    assert work.param_count(c) == {"embed": 40, "stacked": 950, "head": 40, "final": 4}
    assert weights.n_params(c) == 1042
    # weights (950 + 40) * 2 + final 16 + 2 embedding rows 16; K/V of 4
    # elements a position, 1 byte: 2 * ((8 + 1) + 4 + (4 + 1)) * 4; SSM state
    # 2 * 2 * 48 * 4; conv 2 * 2 * 36 * 2; logits 2 * 10 * 4
    got = work.decode_bytes_per_step(c, WIRE, 2, 6, conv_bytes=2, ssm_bytes=4)
    assert got == pytest.approx(2012 + 144 + 768 + 288 + 80)


def test_list_valued_sizes_reach_the_program_and_the_reference(meta, monkeypatch):
    cfg = meta_family.config()
    cfgm = program.model_config(cfg)
    assert cfgm.layer_windows == (0, 8, 8, 16) and cfgm.kv_from == (0, 0, 2, 3)
    hash(cfgm)
    c = harness.model_dict(cfg)
    assert c["layer_windows"] == (0, 8, 8, 16) and c["meta_tokens"] == 4
    seen = []
    grad_fn = ref._grad_fn.__wrapped__

    def spy(c_items, num):
        seen.append(dict(c_items))
        return grad_fn(c_items, num)

    monkeypatch.setattr(ref, "_grad_fn", spy)
    params = jax.jit(lambda k: weights.make(c, k))(weights.seed_key(1, "weights"))
    ref.loss_and_grads(c, params, np.zeros((1, 8), np.int32))
    assert seen == [c]


TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 0.005, "change_gap": 1e-3}


def _cell(kind: str) -> dict:
    cfg = meta_family.config()
    if kind == "train":
        tr = {"kind": "train", "batch": 4, "seq": 32, "mesh": "1x1", "check_steps": 3,
              "ref_rows": 2}
        e2e, limits = ["train_tokens_per_s", "setup_s"], TRAIN_LIMITS
    else:
        tr = {"kind": "decode", "batch": 4, "prompt": 40, "cache_len": 48, "prefill_rows": 2,
              "replay_at": 48, "check_sessions": 2, "mesh": "1x1"}
        e2e, limits = ["decode_tokens_per_s", "token_gap_ms_p95", "setup_s"], {"logit_gap": 0.05}
    return {"name": f"tiny.{kind}.meta", "chips": 1, "config": cfg, "traffic": tr,
            "limits": limits, "end_to_end": e2e, "per_layer": [], "units": tiny.UNITS}


def _run(cell):
    return harness.run(cell, SEED, 0.3, False, time.perf_counter(), require_tpu=False)


@pytest.mark.parametrize("kind", ["train", "decode"])
def test_the_test_family_runs_correct_through_the_harness(meta, kind):
    r = _run(_cell(kind))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1


@pytest.mark.parametrize("fault", ["share", "prefix"])
@pytest.mark.parametrize("kind", ["train", "decode"])
def test_the_test_family_with_a_part_left_out_is_caught(monkeypatch, kind, fault):
    meta_family.install(monkeypatch, **{fault: False})
    r = _run(_cell(kind))
    assert not r["correct"], r["checks"]
