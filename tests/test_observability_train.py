"""TrainLoop's spans: each history entry carries the step's host seconds
and its control path's, the capacity drops it counts are sound on both
step paths, and the benchmark readers read the history."""
import math
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import reshape_moe as rm
from repro.core.skew import SkewParams
from repro.data.synthetic import TokenStream
from repro.models import lm
from repro.runtime.loop import LoopConfig, TrainLoop
from repro.runtime.train import TrainHyper

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _loop(cfg, step_path):
    stream = TokenStream(vocab=cfg.vocab, seq_len=32, global_batch=8,
                         seed=3, class_alpha=2.0)
    reshaper = rm.MoEReshaper(cfg, lm.n_moe_layers(cfg), ep_ranks=2,
                              params=SkewParams(eta=0.0, tau=0.15),
                              phase1_steps=1)
    return TrainLoop(cfg, stream, TrainHyper(),
                     LoopConfig(microbatches=2, step_path=step_path),
                     reshaper=reshaper)


@pytest.mark.parametrize("step_path", ["fused", "granulated"])
def test_history_times_the_step_and_control_and_counts_drops(step_path):
    cfg = get_arch("olmoe-1b-7b-smoke")
    loop = _loop(cfg, step_path)
    t0 = time.perf_counter()
    hist = loop.run(3)
    wall = time.perf_counter() - t0
    assert len(hist) == 3
    for h in hist:
        assert h["t_step_s"] > 0 and h["t_control_s"] > 0
    assert sum(h["t_step_s"] + h["t_control_s"] for h in hist) <= wall
    dropped = sum(float(np.sum(h["dropped"])) for h in hist)
    routed = sum(float(np.sum(h["expert_counts"])) for h in hist)
    assert 0 <= dropped <= routed
    # every token of every microbatch is routed to top_k experts per layer
    assert routed == 3 * 8 * 32 * cfg.moe.top_k * lm.n_moe_layers(cfg)


def test_readers_give_finite_values_on_the_tiny_cell(tmp_path):
    from bench import spec, train
    from bench.tests.harness import tiny_cell
    run = train.run(tiny_cell("train"), 2 ** 31 + 11, 1.0, False,
                    time.perf_counter(), str(tmp_path))
    ctl = spec.reader("control_ms.train")(run)
    drop = spec.reader("drop_share.train")(run)
    assert ctl is not None and math.isfinite(ctl) and ctl > 0
    assert drop is not None and 0 <= drop <= 100
    # a history without the loop's times reads nothing
    bare = dict(run, history=[{k: v for k, v in h.items()
                               if k != "t_control_s"}
                              for h in run["history"]])
    assert spec.reader("control_ms.train")(bare) is None
