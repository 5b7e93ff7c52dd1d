"""chip_smoke.py's phases at smoke size on the CPU, so the chip bring-up
script cannot rot, and its refusal to report a result off the chip."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_arch

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_serve_phase_smoke():
    out = chip_smoke.serve_phase(get_arch("olmoe-1b-7b-smoke"), slots=4,
                                 max_len=128, n_requests=5,
                                 prompt_range=(8, 40), new_range=(4, 12))
    assert out["requests"] == 5 and out["ticks"] > 0
    assert len(out["logit_gaps"]) == 2
    assert max(out["logit_gaps"]) <= chip_smoke.LOGIT_TOL


def test_train_phase_smoke(tmp_path):
    out = chip_smoke.train_phase(get_arch("paper-moe-100m-smoke"),
                                 seq_len=32, global_batch=8, steps=4,
                                 ckpt_every=2, ckpt_root=tmp_path)
    assert len(out["xla"]["loss"]) == len(out["fused"]["loss"]) == 4
    assert out["xla"]["dropped"][0] == out["fused"]["dropped"][0]
    assert not any(tmp_path.iterdir())        # checkpoints cleaned up


def test_placement_phase_smoke():
    dev = jax.devices()[0]
    out = chip_smoke.placement_phase(get_arch("olmoe-1b-7b-smoke"),
                                     [dev, dev], n_requests=6)
    assert out["migrated"] > 0


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "script-alone"])
def test_main_fails_off_chip(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:                  # a directory holding chip_smoke.py only
        script = Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
