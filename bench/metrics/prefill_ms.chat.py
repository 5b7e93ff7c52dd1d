"""Mean milliseconds from a request's admission to its first token (its
prefill, fed through the slot's ticks), over the first tokens committed in
the window, from ServeEngine's ``prefill_s`` and ``first_tokens``
counters.  None where the engine keeps no such counters."""


def read(run):
    c = run.get("counters")
    if not c:
        return None
    a, b = c["start"]["engine"], c["end"]["engine"]
    if "prefill_s" not in a:
        return None
    n = b["first_tokens"] - a["first_tokens"]
    return 1e3 * (b["prefill_s"] - a["prefill_s"]) / n if n else None
