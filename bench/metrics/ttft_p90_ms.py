"""90th percentile of time to first token, from each request's due time,
over every request that arrived in the window."""
from bench.stats import percentile, ttft_s


def read(run):
    if run["kind"] != "serve":
        return None
    v = percentile(ttft_s(run), 90)
    return None if v is None else 1e3 * v
