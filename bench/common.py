"""Pieces every run uses: compile counting, host spans, device facts."""
from __future__ import annotations

import time
from contextlib import contextmanager


class CompileLog:
    """Counts the programs XLA compiled or loaded from the persistent cache,
    from JAX's own monitoring events (after ``chip_smoke.py``), and keeps
    their names.  JAX times a cache load as a backend compile too, so each
    program counts once; one loaded inside the window stalls it just the
    same as one compiled there."""

    def __init__(self):
        import jax
        self.names, self.seconds, self.cache_hits = [], 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(fun_name))
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return len(self.names)

    def since(self, mark: int):
        """Names of the programs compiled or loaded after ``count()`` read
        ``mark``."""
        return self.names[mark:]


class Spans:
    """Host spans of the benchmark's own calls into the program.  Each is a
    ``jax.profiler.TraceAnnotation`` (so a traced run sees it beside the
    device's operations) and its duration is kept per name."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.durations = {}

    @contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0
