"""The comparison that decides ``correct``.

Serving: once the window has closed and the engine is freed, a sample of
finished requests drawn from the seed (the longest one always among them)
is run through the plain reference, which reads for every served token the
gap by which its logit lies below the reference's best.  The mean gap over
all sampled tokens is compared with the cell's limit
(``limits/<cell>.json``); an altered token reads a gap of whole logits, so
one in the sample moves the mean past it.  The mean, not the widest gap:
the widest over a few hundred tokens is set by the one worst near-tie,
which bf16 routing makes in sound runs too, and it did not separate sound
runs from the float8 control.  The largest mean gap of one request is read
for ``calibrate.py``.  Every number compared is printed beside its limit.

Training: the reference follows the run's first steps from the same
weights, batches and plan (``reference_train.py``).  Compared with the
cell's limits: the gap of the norms of the first step's gradient as the
optimizer got it (the program's, worked out from its first moment after
one step), and of each leaf's change over the steps, each by the worst
leaf and measured against the larger of the reference's norm of that leaf
and the median leaf's; and the cosine distance (1 - cosine) between the
program's first gradient and the reference's over all leaves together.
The last is the one that precision moves: from a fresh initialisation,
routing near-ties flip under any rounding, so the loss and the norms read
about alike for bfloat16 and for float8, while the angle between the
gradients grows with the rounding.  The gap of each step's loss is read
for ``calibrate.py``; no fault it could catch reads ten times a sound
run.  Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone under Adam and are left out.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench import gen, reference


def sample(finished: List[Tuple[np.ndarray, np.ndarray]], k: int,
           seed: int) -> List[int]:
    """Indices of ``k`` finished requests: the longest, and ``k - 1`` more
    drawn from the seed."""
    if not finished:
        return []
    sizes = [len(p) + len(o) for p, o in finished]
    longest = int(np.argmax(sizes))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = gen.rng_for(seed, "check")
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + sorted(rest[int(i)] for i in pick)


def pad_len(seqs, multiple: int = 128) -> int:
    n = max(len(p) + len(o) - 1 for p, o in seqs)
    return -(-n // multiple) * multiple


def serve(run: Dict, spec: Dict, seed: int, quant=None) -> Dict[str, Dict]:
    """{name: {"value", "limit"}} for the served tokens' logit gaps that
    the cell's limits name (or, with ``quant``, the control's reading of
    them): their mean over all sampled tokens, and the largest mean of one
    request's tokens."""
    lim = spec["limits"]
    fin = run["finished"]
    idx = sample(fin, int(spec["traffic"].get("check_requests", 8)), seed)
    if not idx:
        return {k: {"value": float("inf"), "limit": v["limit"]}
                for k, v in lim.items()} | \
            {"checked_tokens": {"value": 0, "limit": 1}}
    seqs = [fin[i] for i in idx]
    per_req = reference.serve_gaps(spec["config"], run["params"], seqs,
                                   pad_len(seqs), quant)
    gaps = np.concatenate(per_req)
    vals = {"served_logit_gap_mean": float(gaps.mean()),
            "served_logit_gap_req_max": max(float(g.mean())
                                            for g in per_req)}
    run["gaps"] = gaps
    run["readings"] = vals
    out = {k: {"value": v, "limit": lim[k]["limit"]}
           for k, v in vals.items() if k in lim}
    out["checked_tokens"] = {"value": int(sum(len(o) for _, o in seqs)),
                             "limit": 1}
    return out


def passed(numbers: Dict[str, Dict]) -> bool:
    """A number passes at or under its limit, except counts that must reach
    theirs (named ``checked_*``)."""
    ok = True
    for name, n in numbers.items():
        if name.startswith("checked_"):
            ok &= n["value"] >= n["limit"]
        else:
            ok &= bool(np.isfinite(n["value"])) and n["value"] <= n["limit"]
    return bool(ok)


def leaf_norms(tree) -> Dict[str, float]:
    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
            for k, v in _flat(tree)}


def cos_dist(prog, ref, keep) -> Dict[str, float]:
    """1 - cosine between the program's and the reference's trees, per
    leaf in ``keep`` and over all of them together (key ``""``)."""
    fp = dict(_flat(prog))
    fr = dict(_flat(ref))
    out, dot, pp, rr = {}, 0.0, 0.0, 0.0
    for k in keep:
        a = np.asarray(fp[k], np.float64).ravel()
        b = np.asarray(fr[k], np.float64).ravel()
        d, na, nb = float(a @ b), float(a @ a), float(b @ b)
        out[k] = 1.0 - d / max(float(np.sqrt(na * nb)), 1e-300)
        dot, pp, rr = dot + d, pp + na, rr + nb
    out[""] = 1.0 - dot / max(float(np.sqrt(pp * rr)), 1e-300)
    return out


def _flat(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(k), v) for k, v in flat]


def norm_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep) -> float:
    """Worst leaf: |norm_prog - norm_ref| / max(norm_ref, median norm)."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def moving_leaves(g_ref: Dict[str, float]) -> List[str]:
    med = float(np.median(list(g_ref.values())))
    return [k for k, v in g_ref.items() if v >= 1e-3 * med]


def train_readings(prog: Dict, ref: Dict, p0) -> Dict[str, float]:
    """The numbers, from the program's readings ``prog`` (losses, first
    gradient, final params; host trees) and the reference's.  Besides
    those compared, the first step's loss gap and the first gradient's
    cosine distance in its worst and median leaf, for ``calibrate.py``."""
    import jax
    g_ref = leaf_norms(ref["g_first"])
    keep = moving_leaves(g_ref)
    gaps = [abs(a - b) / abs(b) for a, b in
            zip(prog["losses"], ref["losses"])]
    grad = norm_gap(leaf_norms(prog["g_first"]), g_ref, keep)
    cos = cos_dist(prog["g_first"], ref["g_first"], keep)
    per_leaf = [cos[k] for k in keep]
    d_ref = leaf_norms(jax.tree.map(lambda a, b: np.asarray(a) -
                                    np.asarray(b), ref["params"], p0))
    d_prog = leaf_norms(jax.tree.map(lambda a, b: np.asarray(a) -
                                     np.asarray(b), prog["params"], p0))
    change = norm_gap(d_prog, d_ref, keep)
    return {"loss_gap": max(gaps), "grad_norm_gap": grad,
            "change_norm_gap": change, "grad_cos_dist": cos[""],
            "loss_gap_first": gaps[0],
            "grad_cos_dist_worst_leaf": max(per_leaf),
            "grad_cos_dist_median_leaf": float(np.median(per_leaf))}


def train(run: Dict, spec: Dict, seed: int, quant=None,
          fault=None) -> Dict[str, Dict]:
    """{name: {"value", "limit"}}; ``quant`` puts the control in the
    program's place, ``fault="half"`` the reference with half of each
    microbatch left out."""
    import jax
    from bench import model, reference_train
    c, tr = spec["config"], spec["traffic"]
    first, plans = run["first"], run["plans"]
    n = len(first["loss"])
    stream = run["stream"]
    batches = [stream.batch(k)["tokens"] for k in range(n)]
    n_mb = int(tr["microbatches"])
    if "reference" not in run:      # one reference serves every reading
        p0 = model.make_params(c, gen.jax_seed(seed, "weights"))
        p0_host = jax.tree.map(np.asarray, jax.device_get(p0))
        ref = reference_train.run_steps(c, p0, batches, plans, n_mb)
        run["reference"] = p0_host, {
            "losses": ref["losses"],
            "g_first": jax.device_get(ref["g_first"]),
            "params": jax.device_get(ref["params"])}
        del p0, ref
    p0_host, ref = run["reference"]
    if quant or fault:
        rows = slice(None)
        if fault == "half":
            rows = slice(0, tr["global_batch"] // n_mb // 2)
        ctl = reference_train.run_steps(
            c, model.make_params(c, gen.jax_seed(seed, "weights")),
            batches, plans, n_mb, quant, rows=rows)
        prog = {"losses": ctl["losses"],
                "g_first": jax.device_get(ctl["g_first"]),
                "params": jax.device_get(ctl["params"])}
    else:
        b1 = c["training"]["b1"]
        prog = {"losses": first["loss"],
                "g_first": jax.tree.map(lambda m: m / (1 - b1), first["m1"]),
                "params": first["p_ref"]}
    vals = train_readings(prog, ref, p0_host)
    run["readings"] = vals
    lim = spec["limits"]
    return {k: {"value": vals[k], "limit": lim[k]["limit"]}
            for k in vals if k in lim}
