"""Profiler capture of the measured window, and its reduction to metrics.

``capture`` wraps the window in the JAX profiler (Python tracer off, so
host overhead stays that of the benchmark's own spans).  ``load`` reads the
``.xplane.pb`` with nothing but JAX into plain event tuples, and ``reduce``
turns those into the numbers the per-layer readers and the result line use:

* busy seconds per chip: the union of the intervals in which an operation ran
  on that chip's ``XLA Ops`` line, clipped to the window span;
* the device operations that took most time;
* idle time by what the host was doing: every gap in the union is charged
  to the innermost benchmark span (``bench.*``) open at the gap's midpoint.

Events are ``(plane, line, name, start_ns, duration_ns)``; a recorded sample
is kept in ``tests/data/trace_events.json`` for the reduction's test.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"

Event = Tuple[str, str, str, float, float]


@contextmanager
def capture(log_dir: str):
    """Profile the body; yields, then leaves the trace under ``log_dir``."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> List[Event]:
    """Device operations and benchmark spans of the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return []
    out: List[Event] = []
    for plane in ProfileData.from_file(files[-1]).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if dev and line.name != OP_LINE:
                continue
            for e in line.events:
                if dev or e.name.startswith(SPAN_PREFIX):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


def op_label(name: str) -> str:
    """``%fusion.251 = bf16[80,32,4,2048]{...} fusion(...)`` -> ``fusion.251
    bf16[80,32,4,2048]``: the instruction and the shape it makes."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return (head.lstrip("%") + (" " + shape if shape else ""))[:120]


def _leaves(ivs: List[Tuple[float, float, str]]):
    """Events that contain no other event of their line (a ``while`` op
    spans the operations of its body)."""
    ivs = sorted(ivs, key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, n) in enumerate(ivs):
        nxt = ivs[i + 1] if i + 1 < len(ivs) else None
        if nxt is None or nxt[0] >= e:
            out.append((s, e, n))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def reduce(events: List[Event], top: int = 10) -> Optional[Dict]:
    """{busy_s, window_s, chips, device_ops, idle_gaps} or None where the
    trace holds no window span or no device operation."""
    win = [(s, s + d) for p, _, n, s, d in events
           if n == WINDOW_SPAN and not DEVICE_PLANE.match(p)]
    if not win:
        return None
    w0, w1 = win[0]
    spans = [(s, s + d, n) for p, _, n, s, d in events
             if not DEVICE_PLANE.match(p) and n != WINDOW_SPAN]
    per_chip: Dict[str, list] = defaultdict(list)
    for p, _, n, s, d in events:
        if not DEVICE_PLANE.match(p):
            continue
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            per_chip[p].append((a, b, n))
    if not per_chip:
        return None
    chips = len(per_chip)
    op_time: Dict[str, float] = defaultdict(float)
    busy, idle_by = 0.0, defaultdict(float)
    spans.sort()
    starts = [s for s, _, _ in spans]
    for ivs in per_chip.values():
        for a, b, n in _leaves(ivs):
            op_time[op_label(n)] += b - a
        u = _union([(a, b) for a, b, _ in ivs])
        busy += sum(b - a for a, b in u)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            i = bisect.bisect_right(starts, mid)
            # the benchmark's spans nest a few deep at most
            open_ = [(e - s, n) for s, e, n in spans[max(0, i - 16):i]
                     if mid <= e]
            name = min(open_)[1] if open_ else "no_span"
            idle_by[name] += (b - a)
    ns = 1e-9
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy / chips * ns, "window_s": (w1 - w0) * ns,
            "chips": chips,
            "device_ops": [[n, t / chips * ns] for n, t in ops],
            "idle_gaps": [[n, t / chips * ns] for n, t in gaps]}
