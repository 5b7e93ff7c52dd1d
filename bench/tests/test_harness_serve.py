"""A serving run at CPU size comes out correct, and comes out not correct
when the timed path is broken underneath it or the control stands in."""
import pytest

from bench import check
from bench.tests.harness import execute, tiny_cell

SEED = 2 ** 32 + 99


@pytest.fixture(autouse=True)
def fresh_ticks():
    from repro.engine import serve
    serve.build_slot_tick.cache_clear()
    yield
    serve.build_slot_tick.cache_clear()


def test_sound_run_is_correct(tmp_path):
    out = execute(tiny_cell("serve"), SEED, tmp_path)
    assert out["correct"], out["check"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert out["check"]["checked_tokens"]["value"] > 0
    for m in ("setup_s", "ttft_p90_ms", "tpot_p90_ms"):
        assert out["metrics"][m]["value"] > 0
    assert list(out)[-1] == "check"


def test_altered_token_is_caught(tmp_path, monkeypatch):
    from repro.engine.serve import ServeEngine
    real = ServeEngine._commit_tick

    def commit(self, plan, outs):
        n = real(self, plan, outs)
        for r in plan.part:
            if r.tokens and not getattr(r, "_bent", False):
                r.tokens[-1] = (r.tokens[-1] + 1) % self.cfg.vocab
                r._bent = True
        return n

    monkeypatch.setattr(ServeEngine, "_commit_tick", commit)
    out = execute(tiny_cell("serve"), SEED, tmp_path)
    assert not out["correct"], out["check"]


def test_cache_left_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.models import lm
    real = lm.decode_step

    def stale(params, state, token, cfg, **kw):
        logits, new = real(params, state, token, cfg, **kw)
        return logits, {"caches": state["caches"], "pos": new["pos"]}

    monkeypatch.setattr(lm, "decode_step", stale)
    out = execute(tiny_cell("serve"), SEED, tmp_path)
    assert not out["correct"], out["check"]


def test_control_in_the_programs_place_is_not_correct(tmp_path):
    """The fp8 reference read at the same prompts and tokens reads a gap
    above the cell's limit."""
    from bench import serve
    import time
    cell = tiny_cell("serve")
    run = serve.run(cell, SEED, 2.0, False, time.perf_counter(),
                    str(tmp_path))
    numbers = check.serve(run, cell, SEED, quant="fp8")
    assert not check.passed(numbers), numbers
