"""Mean milliseconds a request waited in ServeEngine's queue for a slot
(submit to admission), over the requests admitted in the window, from the
engine's ``queue_wait_s`` and ``admitted`` counters.  None where the engine
keeps no such counters."""


def read(run):
    c = run.get("counters")
    if not c:
        return None
    a, b = c["start"]["engine"], c["end"]["engine"]
    if "queue_wait_s" not in a:
        return None
    n = b["admitted"] - a["admitted"]
    return 1e3 * (b["queue_wait_s"] - a["queue_wait_s"]) / n if n else None
