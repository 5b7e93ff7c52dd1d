"""Host milliseconds per work tick in ServeEngine's own phases: admission,
planning (the composition decision and the tick's build) and commit (the
token fetch, evictions, bookkeeping), from the engine's ``admit_s``,
``plan_s`` and ``commit_s`` span totals over the window.  None where the
engine keeps no such totals."""
KEYS = ("admit_s", "plan_s", "commit_s")


def read(run):
    c = run.get("counters")
    if not c:
        return None
    a, b = c["start"]["engine"], c["end"]["engine"]
    if any(k not in a for k in KEYS):
        return None
    ticks = b["tick_no"] - a["tick_no"]
    return 1e3 * sum(b[k] - a[k] for k in KEYS) / ticks if ticks else None
