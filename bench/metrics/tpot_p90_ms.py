"""90th percentile of time per output token after the first, over the
window's requests."""
from bench.stats import percentile, tpot_s


def read(run):
    if run["kind"] != "serve":
        return None
    v = percentile(tpot_s(run), 90)
    return None if v is None else 1e3 * v
