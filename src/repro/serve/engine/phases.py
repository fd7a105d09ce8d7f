"""Spans and counters of the engine's drive loop.

``phase(stats, name, **args)`` opens the profiler span ``serve/<name>``
(a :class:`jax.profiler.TraceAnnotation`, so it lands in a profiler trace
on the same clock as the device operations) and, on exit, adds its
``perf_counter_ns`` duration to the ``EngineStats`` field that
``COUNTERS`` names.  The counters run whether or not a trace is being
taken; an inactive annotation costs under a microsecond.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

# span name -> EngineStats field its wall time accumulates into
COUNTERS = {"step": "step_ns", "schedule": "schedule_ns",
            "operands": "operand_ns", "wait": "wait_ns",
            "fetch": "fetch_ns", "sample": "sample_ns",
            "publish": "publish_ns"}


class phase:
    """``with phase(eng.stats, "wait"): ...`` — one span, one counter."""

    __slots__ = ("_stats", "_field", "_span", "_t0")

    def __init__(self, stats, name: str, **args):
        self._stats = stats
        self._field = COUNTERS[name]
        self._span = TraceAnnotation("serve/" + name, **args)

    def annotate(self, **args) -> None:
        """Add span arguments known only once the phase is under way."""
        self._span.set_metadata(**args)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        st, f = self._stats, self._field
        setattr(st, f, getattr(st, f) + time.perf_counter_ns() - self._t0)
        return self._span.__exit__(*exc)
