"""Host spans of the serving program on the profiler's clock.

:func:`span` opens a ``jax.profiler.TraceAnnotation`` named ``xpk.<name>``:
inside a ``jax.profiler`` session it lands in the same trace as the device
planes, on the same clock, so a device idle gap can be read against what
the host was doing; outside one it costs about a microsecond.  Every span
of the program goes through it, so the prefix lives here alone.  One span
per front-door round or scheduler step, never per slot or per token:

* ``xpk.pump`` — ``FrontDoor._pump_once``, with ``xpk.pump.intake``
  (refill, intake, preemption scan), ``xpk.pump.admit`` and
  ``xpk.pump.stream`` (token streaming and gauges);
* ``xpk.sched.step`` — ``BatchScheduler.step``, with ``xpk.sched.admit``,
  ``xpk.sched.prepare`` (page opens, copy-on-write, feed), ``xpk.sched.
  dispatch``, ``xpk.sched.readback`` and ``xpk.sched.commit``;
* ``xpk.gc`` — every CPython collection, with its ``generation``
  (:func:`install_gc_hook`).
"""

from __future__ import annotations

import gc
import time
import weakref

import jax

SPAN_PREFIX = "xpk."


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """The host span ``xpk.<name>`` (a context manager); ``meta`` is
    attached to the trace event."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **meta)


class _GcSpans:
    """A ``gc.callbacks`` entry: an ``xpk.gc`` span from each collection's
    start to its stop, and its length added to every live registry's
    ``gc_pause_seconds_total{generation}``."""

    def __init__(self):
        self.counters = weakref.WeakSet()
        self._open = None  # (annotation, start) of the running collection

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = span("gc", generation=info["generation"])
            ann.__enter__()
            self._open = (ann, time.perf_counter())
            return
        if self._open is None:  # installed mid-collection
            return
        ann, t0 = self._open
        self._open = None
        ann.__exit__(None, None, None)
        dt = time.perf_counter() - t0
        gen = str(info["generation"])
        for counter in list(self.counters):
            counter.inc(dt, gen)


_GC_SPANS = _GcSpans()


def install_gc_hook(registry) -> None:
    """Span and count every garbage collection of this process into
    ``registry`` (idempotent: one callback per process, one counter per
    registry)."""
    _GC_SPANS.counters.add(registry.counter(
        "gc_pause_seconds_total", "CPython garbage-collection pauses",
        ("generation",)))
    if _GC_SPANS not in gc.callbacks:
        gc.callbacks.append(_GC_SPANS)
