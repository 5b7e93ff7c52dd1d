"""Model FLOPs of the steps in the window (6 N_active per token plus
attention; recomputation not counted) over chips x peak."""
from bench.stats import mfu_percent


def read(run):
    return mfu_percent(run) if run["kind"] == "train" else None
