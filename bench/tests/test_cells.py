"""A whole run of each kind of cell at a size the CPU holds, past the look
for a chip: correct as the program stands, and not correct with the timed
path broken underneath or with the control in the program's place.

The tiny limits here were set the way the cells' own were: between the
program's readings on four seeds and the control's.  Train, hybrid: loss
1.0e-4 to 2.6e-4, grad norm 6e-4 to 7e-3, change 9e-4 to 1.7e-3, against
the control's loss 2.0e-3 to 6.9e-3, grad norm 0.52 to 0.60, change 0.004
to 0.029.  Train, ssm: loss 5.8e-5 to 1.7e-4, grad norm 8e-4 to 2.1e-3,
change 1.6e-3 to 8.9e-3, against the control's loss 8.7e-4 to 2.3e-3, grad
norm 0.011 to 0.031, change 0.016 to 0.029.  Decode: logit gap 0 hybrid,
0.016 to 0.022 ssm, against the control's 0.26 to 0.79.

Run: ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""

import time

import jax.numpy as jnp
import pytest

import tiny
from bench import control, harness, program

SEED = 2**33 + 5


def _run(cell, **kw):
    return harness.run(cell, SEED, 0.3, False, time.perf_counter(), require_tpu=False, **kw)


def _patch_train_step(monkeypatch, wrap):
    make = program.dstep.make_train_step

    def faulty(cfg, mesh, **kw):
        return wrap(make(cfg, mesh, **kw))

    monkeypatch.setattr(program.dstep, "make_train_step", faulty)


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_train_cell_is_correct(family):
    r = _run(tiny.train_cell(family))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch, family):
    _patch_train_step(monkeypatch, lambda step: lambda state, batch: (state, step(state, batch)[1]))
    r = _run(tiny.train_cell(family))
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_half_the_batch_left_out_is_caught(monkeypatch, family):
    def half(step):
        return lambda state, batch: step(
            state, {"tokens": batch["tokens"][: batch["tokens"].shape[0] // 2]})

    _patch_train_step(monkeypatch, half)
    r = _run(tiny.train_cell(family))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_decode_cell_is_correct(family):
    r = _run(tiny.decode_cell(family))
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"decode_tokens_per_s", "token_gap_ms_p95", "setup_s"}


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch, family):
    make = program.dstep.make_serve_step

    def faulty(cfg, mesh):
        serve = make(cfg, mesh)

        def step(params, batch, cache):
            logits, cache = serve(params, batch, cache)
            return jnp.roll(logits, 1, axis=-1), cache  # every token one id off

        return step

    monkeypatch.setattr(program.dstep, "make_serve_step", faulty)
    r = _run(tiny.decode_cell(family))
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_train_control_and_half_batch_are_not_correct(family):
    got = control.train_readings(tiny.train_cell(family), SEED)
    assert set(got) == {"control", "half_batch"}
    for reading in got.values():
        assert reading["correct"] is False, reading["checks"]


@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_decode_control_is_not_correct(family):
    r = _run(tiny.decode_cell(family), control=True)
    assert r["correct"], r["checks"]
    assert r["control"]["correct"] is False, r["control"]["checks"]
