"""Mean host span of ServeEngine.tick() in the window (engine layer)."""


def read(run):
    spans = run.get("tick_spans") or []
    return 1e3 * sum(spans) / len(spans) if spans else None
