"""Arithmetic the metric readers share."""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional


def percentile(xs: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (``repro.core.scheduler.percentile``): a
    value some request really saw.  None for no samples."""
    xs = sorted(float(x) for x in xs)
    if not xs:
        return None
    rank = max(int(math.ceil(q / 100.0 * len(xs))), 1)
    return xs[rank - 1]


def ttft_s(run: Dict) -> List[float]:
    """Due time to first token of every request that arrived in the
    window; one that never produced a token counts the wait to the run's
    end."""
    return [(r["t_first"] if r["t_first"] is not None else run["t_end"])
            - r["due"] for r in run["requests"]]


def tpot_s(run: Dict) -> List[float]:
    """Time per output token after the first, of every window request
    that finished with two tokens or more."""
    return [(r["t_done"] - r["t_first"]) / (r["n_out"] - 1)
            for r in run["requests"]
            if r["t_done"] is not None and r["t_first"] is not None
            and r["n_out"] >= 2]


def idle_percent(run: Dict) -> Optional[float]:
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mfu_percent(run: Dict) -> Optional[float]:
    """Model FLOPs of the window over chips x bf16 peak x window."""
    if not run.get("peak") or run["window_s"] <= 0:
        return None
    peak = run["peak"]["bf16_flops"] * run["chips"]
    return 100.0 * run["model_flops"] / run["window_s"] / peak
