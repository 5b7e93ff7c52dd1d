"""``bench/run.py`` refuses to measure anywhere but on the chips."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ARGS = ["--workload", "olmoe-chat", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_exits_nonzero_without_a_tpu():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        spec.cell(spec.benchmark(), "no-such-cell")


def test_a_compile_inside_the_window_fails_the_run(monkeypatch, capsys):
    from bench import run
    out = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
           "device": {}, "check": {"loss_gap": {"value": 0.0, "limit": 1.0}},
           "info": {"compiles_in_window": 1,
                    "compiled_in_window": ["jit(convert_element_type)"]}}
    monkeypatch.setattr(run, "chips_or_exit", lambda chips: None)
    monkeypatch.setattr(run, "set_compile_cache", lambda: "")
    monkeypatch.setattr(run, "execute", lambda *a, **k: dict(out))
    assert run.main(ARGS) == 4
    printed = capsys.readouterr()
    assert _no_result(printed.out)
    assert "jit(convert_element_type)" in printed.err
    assert printed.err.strip().splitlines()[-1].startswith("loss_gap")
