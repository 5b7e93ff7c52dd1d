"""Share of the window's routed expert assignments (tokens x top-k, every
MoE layer) dropped at expert capacity: the sum of the steps' ``dropped``
over the sum of their ``expert_counts``, as the step counts them on the
device and TrainLoop keeps them in its history."""
import numpy as np


def read(run):
    hist = run.get("history") or []
    if not hist or any("dropped" not in h or "expert_counts" not in h
                       for h in hist):
        return None
    routed = sum(float(np.sum(h["expert_counts"])) for h in hist)
    dropped = sum(float(np.sum(h["dropped"])) for h in hist)
    return 100.0 * dropped / routed if routed else None
