"""The benchmark's FLOP arithmetic agrees with the program's analytic count."""
import pytest

from bench import flops, spec


@pytest.mark.parametrize("name", ["paper-moe-100m", "olmoe-1b-7b"])
def test_model_flops_match_repro_analysis(name):
    from bench import model
    from repro.analysis import flops as repro_flops
    from repro.configs.base import ShapeCfg
    c = spec.load_json(spec.BENCH / "configs" / f"{name}.json")
    cfg = model.arch(c)
    shape = ShapeCfg("t", 2048, 32, "train", microbatches=16)
    want = repro_flops.step_flops(cfg, shape).model_flops
    got = flops.train_flops(c, shape.tokens, shape.seq_len)
    assert got["dense"] == pytest.approx(want, rel=1e-12)
    attn = repro_flops._attn_score_flops(cfg, shape.seq_len / 2) * \
        cfg.num_layers * shape.tokens * 3
    assert got["attention"] == pytest.approx(attn, rel=1e-12)


def test_serve_flops_sum_positions():
    c = spec.load_json(spec.BENCH / "configs" / "olmoe-1b-7b.json")
    one = [flops.serve_flops(c, p, p + 1) for p in range(10)]
    assert flops.serve_flops(c, 0, 10) == pytest.approx(sum(one))
    assert flops.serve_flops(c, 5, 5) == 0.0
    # 8 layers of 67 M active weights (attention, router, 8 experts) and
    # the 103 M head
    n = flops.active_params(c)
    assert 0.63e9 < n < 0.65e9


def test_peaks_table_refuses_unknown_device():
    from bench import peaks
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
