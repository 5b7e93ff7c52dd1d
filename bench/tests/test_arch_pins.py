"""The MoE decoder's arithmetic is pinned: the same seed gives the same
weights, reference hidden states, served-token gaps, first reference
training step, sizes and FLOP counts as the pins in
``data/moe_decoder_pins.json``, which were recorded before the block moved
out of the shared harness into ``bench/arch/moe_decoder.py``.

Integers and FLOP counts compare exactly; float sums at a relative 1e-6, so
that moving a jit boundary may change rounding but not the mathematics.

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 bench/tests/test_arch_pins.py

writes the pins anew from the tree it runs in.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import flops, model, reference, reference_train, spec  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PINS = DATA / "moe_decoder_pins.json"
TINY = ["tiny-moe", "tiny-moe-train"]
REAL = ["olmoe-1b-7b", "paper-moe-100m"]
RTOL = 1e-6


def _config(name: str) -> dict:
    path = DATA / f"{name}.json" if name in TINY else \
        spec.BENCH / "configs" / f"{name}.json"
    return spec.load_json(path)


def _sums(x) -> list:
    a = np.asarray(x, np.float64)
    return [float(a.sum()), float(np.abs(a).sum())]


def _by_path(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): v for k, v in flat}


def sizes(c: dict) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(
        model.layout(c), is_leaf=model._is_leaf)
    return {"layout": {jax.tree_util.keystr(k): list(v[0])
                       for k, v in flat},
            "param_bytes": model.param_bytes(c),
            "active_params": flops.active_params(c),
            "serve_flops_0_1024": flops.serve_flops(c, 0, 1024),
            "train_flops_65536_2048": flops.train_flops(c, 65536, 2048)}


def _row(c: dict, n: int) -> np.ndarray:
    return ((np.arange(n) * 37 + 5) % c["vocab_size"]).astype(np.int32)


def _plans(c: dict):
    """An identity plan with one expert split over a spare slot, the copy
    that split needs before the step, and one copy after it."""
    nl, e = c["num_hidden_layers"], c["num_experts"]
    r, spare = c["program"]["max_replicas"], e
    ps = np.tile(np.arange(e, dtype=np.int32)[None, :, None], (nl, 1, r))
    pc = np.ones((nl, e, r), np.float32)
    ps[0, 1, 1], pc[0, 1, 0] = spare, 0.5
    return [(ps, pc, [(0, 1, spare)]), (ps, pc, [(nl - 1, 2, spare + 1)])]


def weights(c: dict) -> dict:
    return {k: _sums(v) for k, v in _by_path(model.make_params(c, 0)).items()}


def forward(c: dict) -> dict:
    params = model.make_params(c, 0)
    row = _row(c, 24)
    h = reference.hidden(c, params, row[None])
    gaps = reference.serve_gaps(c, params, [(row[:16], row[16:])], 32)
    return {"hidden": _sums(h), "serve_gaps": _sums(np.concatenate(gaps))}


def train_step(c: dict) -> dict:
    import jax
    params = jax.tree.map(lambda x: x.astype(np.float32),
                          model.make_params(c, 0))
    tokens = np.stack([_row(c, 33)[i:i + 16] for i in range(4)])
    tokens = (tokens + np.arange(4, dtype=np.int32)[:, None] * 11) \
        % c["vocab_size"]
    out = reference_train.run_steps(c, params, [tokens], _plans(c), 2)
    norms = {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
             for k, v in _by_path(out["g_first"]).items()}
    after = {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
             for k, v in _by_path(out["params"]).items()}
    return {"loss": out["losses"][0], "g_first_norms": norms,
            "params_norms": after}


def record() -> dict:
    pins = {}
    for name in TINY:
        c = _config(name)
        pins[name] = {"sizes": sizes(c), "weights": weights(c),
                      "forward": forward(c)}
        if "training" in c:
            pins[name]["train_step"] = train_step(c)
    for name in REAL:
        pins[name] = {"sizes": sizes(_config(name))}
    return pins


@pytest.fixture(scope="module")
def pins() -> dict:
    return spec.load_json(PINS)


def _close(got, want, where=""):
    if isinstance(want, dict):
        assert set(got) == set(want), where
        for k in want:
            _close(got[k], want[k], f"{where}/{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), where
        for i, (a, b) in enumerate(zip(got, want)):
            _close(a, b, f"{where}[{i}]")
    else:
        assert got == pytest.approx(want, rel=RTOL), where


@pytest.mark.parametrize("name", TINY + REAL)
def test_sizes_and_flops_are_exact(name, pins):
    """Every layout shape, the weight bytes, the active weights and the
    serving and training FLOP counts, to the last digit."""
    assert sizes(_config(name)) == pins[name]["sizes"]


@pytest.mark.parametrize("name", TINY)
def test_weights_from_the_seed(name, pins):
    """Each leaf's sum and absolute sum: the flatten order of the layout
    fixes each leaf's key, so a reordered tree draws other weights."""
    _close(weights(_config(name)), pins[name]["weights"])


@pytest.mark.parametrize("name", TINY)
def test_reference_hidden_and_serve_gaps(name, pins):
    _close(forward(_config(name)), pins[name]["forward"])


def test_first_reference_training_step(pins):
    name = "tiny-moe-train"
    _close(train_step(_config(name)), pins[name]["train_step"])


if __name__ == "__main__":
    PINS.write_text(json.dumps(record(), indent=1, sort_keys=True) + "\n")
    print(f"wrote {PINS}")
