"""A new architecture arrives as files only: a module under the benchmark
root's ``arch/`` and a configuration naming it.  The dispatchers of
``model``, ``reference``, ``reference_train`` and ``flops`` reach it with no
harness file edited; a configuration naming no architecture, a missing
file or an incomplete module fails with a message naming it."""
import pytest

from bench import model, reference_train, spec
from bench.tests.harness import execute, tiny_cell

SEED = 2 ** 32 + 17
MARK = '''

CALLS = []


def _marked(f):
    def call(*a, **k):
        CALLS.append(f.__name__)
        return f(*a, **k)
    return call


for _name in ("arch", "layout", "hidden", "active_params", "attn_flops",
              "run_steps"):
    globals()[_name] = _marked(globals()[_name])
'''


def _source() -> str:
    return (spec.BENCH / "arch" / "moe_decoder.py").read_text()


def _checkout(tmp_path, monkeypatch, name: str, source: str):
    """A benchmark root holding one architecture module, made the root the
    harness loads architectures from."""
    root = tmp_path / "checkout"
    arch = root / spec.BENCH.name / "arch"
    arch.mkdir(parents=True)
    (arch / f"{name}.py").write_text(source)
    monkeypatch.setattr(spec, "ROOT", root)
    return root


def test_a_new_architecture_module_serves_a_cell(tmp_path, monkeypatch):
    """A copy of ``moe_decoder`` under another name, with a marker on its
    interface, drives the tiny serving cell through ``run.execute``."""
    _checkout(tmp_path, monkeypatch, "marked_decoder", _source() + MARK)
    cell = tiny_cell("serve")
    cell["config"]["architecture"] = "marked_decoder"
    out = execute(cell, SEED, tmp_path)
    assert out["correct"], out["check"]
    mod = model.module(cell["config"])
    assert mod.__file__.startswith(str(tmp_path))
    assert {"arch", "layout", "hidden", "active_params",
            "attn_flops"} <= set(mod.CALLS), sorted(set(mod.CALLS))


@pytest.mark.parametrize("name, source, words", [
    (None, None, ["tiny-moe", "names no architecture"]),
    ("no_such_block", None, ["tiny-moe", "arch/no_such_block.py"]),
    ("headless", "def arch(c): pass\ndef layout(c): pass\n",
     ["arch/headless.py", "hidden", "active_params", "attn_flops"]),
], ids=["no_key", "no_file", "no_interface"])
def test_a_missing_architecture_fails_naming_it(tmp_path, monkeypatch,
                                                name, source, words):
    if source is not None:
        _checkout(tmp_path, monkeypatch, name, source)
    cell = tiny_cell("serve")
    cell["config"].pop("architecture")
    if name is not None:
        cell["config"]["architecture"] = name
    with pytest.raises(SystemExit) as err:
        execute(cell, SEED, tmp_path)
    for w in words:
        assert w in str(err.value), (w, str(err.value))


def test_a_training_cell_needs_run_steps(tmp_path, monkeypatch):
    """An architecture without a training reference serves, but a training
    cell's check exits with a message, not a traceback."""
    src = _source() + "\ndel run_steps\n"
    _checkout(tmp_path, monkeypatch, "serve_only", src)
    c = dict(tiny_cell("train")["config"], architecture="serve_only")
    assert model.arch(c).moe is not None
    with pytest.raises(SystemExit) as err:
        reference_train.run_steps(c, None, [], [], 1)
    assert "run_steps" in str(err.value)
    assert "arch/serve_only.py" in str(err.value)
