"""Host milliseconds per step inside the Reshape controller's calls."""


def read(run):
    t = run.get("reshape_s")
    steps = run.get("steps")
    return 1e3 * t / steps if t is not None and steps else None
