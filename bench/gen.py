"""Traffic generation: every input a run sends is drawn here from ``--seed``.

The samplers are copied from ``repro.engine.loadgen`` (Poisson and bursty
arrivals, bounded-Pareto lengths, shared-preamble prompts) and the skewed
token stream from ``repro.data.synthetic.TokenStream``, so that a change to
the program cannot move the yardstick.  A traffic file (``traffic/<mix>.json``)
holds only parameters; :func:`serve_requests` and :func:`token_batches` are
the one generator that reads them.

Every seed gets the same work: lengths and inter-arrival gaps are the
quantiles of their distributions (stratified, not sampled), and the seed only
shuffles their order and draws the token ids.  Two seeds therefore differ in
which request comes when and what it says, never in how much there is to do.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def rng_for(seed: int, *tags) -> np.random.Generator:
    """An independent generator per (seed, purpose): any whole seed, of any
    size, with string or int tags."""
    words = [int(seed) & (2 ** 64 - 1)]
    for t in tags:
        words.append(int.from_bytes(str(t).encode()[:8].ljust(8, b"\0"),
                                    "little"))
    return np.random.default_rng(np.random.SeedSequence(words))


def jax_seed(seed: int, *tags) -> int:
    """A 31-bit seed for ``jax.random`` derived from a seed of any size."""
    return int(rng_for(seed, "jax", *tags).integers(0, 2 ** 31 - 1))


# ------------------------------------------------------------- distributions
# Inverse CDFs, evaluated at stratified points ((i + 0.5) / n): the n values
# are the distribution's n-quantiles, the same for every seed.

def bounded_pareto_quantiles(n: int, lo: int, hi: int,
                             alpha: float) -> np.ndarray:
    """Bounded Pareto on [lo, hi] (the ``heavy_tail_lengths`` law of
    ``repro.engine.loadgen``), as whole numbers."""
    u = (np.arange(n) + 0.5) / n
    la, ha = float(lo) ** alpha, float(hi) ** alpha
    x = (-(u * (ha - la) - ha) / (ha * la)) ** (-1.0 / alpha)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def exponential_quantiles(n: int, mean: float) -> np.ndarray:
    """Inter-arrival gaps of a Poisson process (``poisson_arrivals``)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) * mean


def lengths(spec: Dict, n: int) -> np.ndarray:
    kind = spec.get("dist", "bounded_pareto")
    if kind == "bounded_pareto":
        return bounded_pareto_quantiles(n, spec["lo"], spec["hi"],
                                        spec.get("alpha", 1.2))
    raise ValueError(f"unknown length distribution {kind!r}")


def _starts(gaps: np.ndarray, seconds: float) -> np.ndarray:
    """Arrival times from gaps: the first at 0, the gaps scaled to fill
    ``seconds``, so the gap after the last arrival ends the phase."""
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * seconds / gaps.sum()


def arrival_offsets(spec: Dict, n: int, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """n arrival times in [0, seconds): Poisson gaps (shuffled quantiles,
    scaled so that the n-th arrival lands exactly at ``seconds``) or bursts
    of ``burst`` requests at Poisson burst starts."""
    kind = spec.get("arrival", "poisson")
    if kind == "poisson":
        return _starts(rng.permutation(exponential_quantiles(n, 1.0)),
                       seconds)
    if kind == "bursty":
        burst = int(spec["burst"])
        nb = -(-n // burst)
        starts = _starts(rng.permutation(exponential_quantiles(nb, 1.0)),
                         seconds)
        return np.repeat(starts, burst)[:n]
    raise ValueError(f"unknown arrival process {kind!r}")


# ------------------------------------------------------------------- serving

@dataclasses.dataclass
class GenRequest:
    due: float            # seconds after its phase starts
    prompt: np.ndarray    # int32 token ids
    max_new: int
    phase: str            # "warm" | "window" | "after"


def serve_requests(traffic: Dict, vocab: int, seed: int, phase: str,
                   seconds: float, t0: float = 0.0) -> List[GenRequest]:
    """The requests of one phase: ``round(rate * seconds)`` of them, due in
    [t0, t0 + seconds).  The phases draw from independent streams of the
    seed, so the window's requests do not depend on how long warm-up is."""
    rate = float(traffic["rate"])
    n = max(int(round(rate * seconds)), 1)
    rng = rng_for(seed, "serve", phase)
    at = arrival_offsets(traffic, n, seconds, rng)
    plens = rng.permutation(lengths(traffic["prompt"], n))
    outs = rng.permutation(lengths(traffic["output"], n))
    shared = traffic.get("shared_prefix")
    pre = None
    if shared:
        pre = rng_for(seed, "serve", "preambles").integers(
            1, vocab, size=(int(shared["count"]), int(shared["len"])))
    reqs = []
    for i in range(n):
        body = rng.integers(1, vocab, size=int(plens[i])).astype(np.int32)
        if pre is not None:
            body = np.concatenate(
                [pre[int(rng.integers(0, len(pre)))], body]).astype(np.int32)
        reqs.append(GenRequest(float(t0 + at[i]), body, int(outs[i]), phase))
    return reqs


def max_request_len(traffic: Dict) -> int:
    """The longest prompt + output the mix can send."""
    pre = traffic.get("shared_prefix")
    return (traffic["prompt"]["hi"] + traffic["output"]["hi"]
            + (int(pre["len"]) if pre else 0))


# ------------------------------------------------------------------ training

class TokenBatches:
    """Class-skewed LM batches (``TokenStream`` of ``repro.data.synthetic``):
    each row draws a class from a Zipf law of exponent ``class_alpha`` and
    its tokens from that class's slice of the vocabulary, so routing is
    skewed the way Reshape is meant to correct.  Rows all differ.  Offers
    the iterator interface ``TrainLoop`` reads (``next``, ``seq_len``,
    ``state``, ``restore``)."""

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        self.vocab = vocab
        self.seq_len = int(traffic["seq_len"])
        self.global_batch = int(traffic["global_batch"])
        self.n_classes = int(traffic.get("n_classes", 8))
        self.class_alpha = float(traffic.get("class_alpha", 0.0))
        self.seed = seed
        self.step = 0

    def class_probs(self) -> np.ndarray:
        if self.class_alpha <= 0:
            return np.full(self.n_classes, 1.0 / self.n_classes)
        w = 1.0 / np.arange(1, self.n_classes + 1) ** self.class_alpha
        return w / w.sum()

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = rng_for(self.seed, "train", step)
        b, s, v = self.global_batch, self.seq_len, self.vocab
        cls = rng.choice(self.n_classes, size=(b,), p=self.class_probs())
        lo = (cls * (v // self.n_classes))[:, None]
        tokens = lo + rng.integers(1, v // self.n_classes, size=(b, s))
        return {"tokens": tokens.astype(np.int32),
                "classes": cls.astype(np.int32)}

    def next(self) -> Dict[str, np.ndarray]:
        out = self.batch(self.step)
        self.step += 1
        return out

    def state(self) -> dict:
        return {"seed": self.seed, "step": self.step}

    def restore(self, state: dict) -> "TokenBatches":
        self.seed, self.step = state["seed"], state["step"]
        return self
