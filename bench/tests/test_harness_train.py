"""A training run at CPU size comes out correct, and comes out not correct
when the step is broken underneath it or the control stands in."""
import time

from bench import check
from bench.tests.harness import execute, tiny_cell

SEED = 2 ** 31 + 5


def test_sound_run_is_correct(tmp_path):
    out = execute(tiny_cell("train"), SEED, tmp_path)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["train_tok_s"]["value"] > 0
    assert set(out["check"]) == {"grad_norm_gap", "change_norm_gap",
                                 "grad_cos_dist"}


def test_state_left_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.optim import adamw
    real = adamw.apply

    def frozen(params, grads, state, cfg, lr_scale=1.0):
        _, _, metrics = real(params, grads, state, cfg, lr_scale)
        return params, state, metrics

    monkeypatch.setattr(adamw, "apply", frozen)
    out = execute(tiny_cell("train"), SEED, tmp_path)
    assert not out["correct"], out["check"]


def test_half_the_batch_left_out_is_caught(tmp_path, monkeypatch):
    from repro.runtime import train
    real = train.loss_fn

    def half(params, batch, *a, **k):
        tok = batch["tokens"]
        return real(params, dict(batch, tokens=tok[: tok.shape[0] // 2]),
                    *a, **k)

    monkeypatch.setattr(train, "loss_fn", half)
    out = execute(tiny_cell("train"), SEED, tmp_path)
    assert not out["correct"], out["check"]


def test_control_in_the_programs_place_is_not_correct(tmp_path):
    from bench import train
    cell = tiny_cell("train")
    run = train.run(cell, SEED, 1.0, False, time.perf_counter(),
                    str(tmp_path))
    numbers = check.train(run, cell, SEED, quant="fp8")
    assert not check.passed(numbers), numbers
