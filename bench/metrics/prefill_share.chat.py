"""Share of the engine's tick time spent in ticks composed for prefill
(the rest decode, plain or speculative), from ServeEngine's
``prefill_tick_s`` and ``decode_tick_s`` span totals over the window.
None where the engine keeps no such totals."""


def read(run):
    c = run.get("counters")
    if not c:
        return None
    a, b = c["start"]["engine"], c["end"]["engine"]
    if "prefill_tick_s" not in a:
        return None
    pre = b["prefill_tick_s"] - a["prefill_tick_s"]
    dec = b["decode_tick_s"] - a["decode_tick_s"]
    return 100.0 * pre / (pre + dec) if pre + dec > 0 else None
