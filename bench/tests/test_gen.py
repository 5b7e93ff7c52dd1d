"""The traffic generator: same seed, same inputs; every seed, same work."""
import numpy as np
import pytest

from bench import gen, spec

CHAT = spec.load_json(spec.BENCH / "traffic" / "chat.json")
BIG = 2 ** 33 + 12345


@pytest.mark.parametrize("phase", ["warm", "window", "after"])
def test_same_seed_same_stream(phase):
    traffic = CHAT
    a = gen.serve_requests(traffic, 50304, BIG, phase, 30.0)
    b = gen.serve_requests(traffic, 50304, BIG, phase, 30.0)
    assert len(a) == len(b) == round(traffic["rate"] * 30.0)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("seed", [7, BIG])
def test_lengths_in_bounds_and_arrivals_in_window(seed):
    traffic = CHAT
    reqs = gen.serve_requests(traffic, 50304, seed, "window", 30.0)
    p, o = traffic["prompt"], traffic["output"]
    assert all(p["lo"] <= len(r.prompt) <= p["hi"] for r in reqs)
    assert all(o["lo"] <= r.max_new <= o["hi"] for r in reqs)
    assert all(0 < t < 50304 for r in reqs for t in r.prompt)
    due = [r.due for r in reqs]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 30.0
    need = gen.max_request_len(traffic) + 16
    assert need <= traffic["serving"]["max_len"]


def test_seeds_differ_in_order_not_in_work():
    a = gen.serve_requests(CHAT, 50304, 1, "window", 30.0)
    b = gen.serve_requests(CHAT, 50304, 2, "window", 30.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_phases_are_independent_streams():
    w = gen.serve_requests(CHAT, 50304, 3, "window", 30.0)
    v = gen.serve_requests(CHAT, 50304, 3, "warm", 30.0)
    assert not np.array_equal(w[0].prompt, v[0].prompt)


def test_token_batches_skewed_deterministic_rows_differ():
    tr = spec.load_json(spec.BENCH / "traffic" / "train-skew.json")
    s1 = gen.TokenBatches(tr, 32000, BIG)
    s2 = gen.TokenBatches(tr, 32000, BIG)
    a, b = s1.next(), s2.next()
    assert np.array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (tr["global_batch"], tr["seq_len"])
    assert len({r.tobytes() for r in a["tokens"]}) == tr["global_batch"]
    assert s1.next()["tokens"].tobytes() != a["tokens"].tobytes()
    # class 0 is the hottest slice of the vocabulary
    cls = np.concatenate([gen.TokenBatches(tr, 32000, s).batch(0)["classes"]
                          for s in range(20)])
    assert np.bincount(cls, minlength=8)[0] > np.bincount(
        cls, minlength=8)[7] * 3
