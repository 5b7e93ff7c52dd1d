"""Host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation``
with ``step=True``), so a profile shows it on the host timeline beside
the device's ``XLA Ops``, and it reads the host clock once on entry and
once on exit.  After the ``with`` block its host duration is ``seconds``;
``into=(obj, name)`` also adds that duration to the float attribute
``obj.name``, the running total a counter reads.  Attributes given at
entry, or later through ``set``, ride on the profiler event.

There is no event list, exporter or switch: with the profiler off a span
costs the annotation's enter and exit and two clock reads.  A span never
waits for the device; it times whatever the code inside it already waits
for.
"""
from __future__ import annotations

import time
from typing import Any, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation


class span:
    __slots__ = ("_ann", "_into", "_t0", "seconds")

    def __init__(self, name: str, into: Optional[Tuple[Any, str]] = None,
                 step: bool = False, **attrs):
        cls = StepTraceAnnotation if step else TraceAnnotation
        self._ann = cls(name, **attrs)
        self._into = into
        self.seconds = 0.0

    def set(self, **attrs) -> None:
        """Attach attributes known only inside the span (a tick's mode is
        decided after it starts, a step's number read after it starts)."""
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._into is not None:
            obj, name = self._into
            setattr(obj, name, getattr(obj, name) + self.seconds)
