"""Serving observability: metrics registry, lifecycle tracing, flight
recorder and profiler hooks.

One :class:`Telemetry` bundle threads through the whole serving stack —
:class:`~repro.serving.BatchScheduler`, the
:class:`~repro.server.FrontDoor` admission layer and the HTTP transport —
so a single object answers "what is this server doing right now":

* :attr:`Telemetry.metrics` — a :class:`MetricsRegistry` of host-side
  counters / gauges / log-bucket histograms, exposed as Prometheus text
  at ``GET /metrics`` and nested into ``GET /stats``;
* :attr:`Telemetry.tracer` — request-lifecycle span events
  (submit→admit→prefill→first-token→decode→preempt/readmit→finish) to
  pluggable sinks (JSONL via ``--trace-out``, Perfetto export);
* :attr:`Telemetry.recorder` — a flight recorder of recent per-slot
  events, dumped to a postmortem file when a scheduler/page-pool
  invariant guard raises;
* :attr:`Telemetry.profiler` — opt-in ``jax.profiler`` capture of N
  decode steps (``--profile-steps`` / ``--profile-dir``);
* :func:`span` — ``xpk.*`` host spans of the front door, the scheduler
  and garbage collection, on the profiler's clock (:mod:`repro.obs.spans`).

Everything here is host-side bookkeeping over the scheduler's existing
Python loop: attaching telemetry never touches jitted code, never changes
a decoded token or a booked joule, and never adds a compile — invariants
held by ``tests/test_obs.py`` and the gated ``obs_overhead_rel`` ratio in
``benchmarks/serving_load.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.obs.metrics import (
    LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_buckets,
    render_prometheus,
)
from repro.obs.profiler import StepProfiler
from repro.obs.recorder import FlightRecorder
from repro.obs.spans import install_gc_hook, span
from repro.obs.trace import (
    GUARD,
    JsonlSink,
    ListSink,
    Tracer,
    load_jsonl,
    perfetto_export,
    write_perfetto,
)


@dataclasses.dataclass
class Telemetry:
    """The telemetry bundle threaded through scheduler / front door / HTTP."""

    metrics: MetricsRegistry
    tracer: Tracer
    recorder: Optional[FlightRecorder] = None
    profiler: Optional[StepProfiler] = None

    @classmethod
    def create(cls, *, flight_dir: str = ".",
               flight_ring: int = 256,
               profiler: Optional[StepProfiler] = None) -> "Telemetry":
        """Standard bundle: registry + tracer + armed flight recorder (the
        recorder listens to the tracer, so guard-site dumps always have
        recent history even when no external trace sink is attached), and
        the process's garbage collections spanned and counted into the
        registry."""
        recorder = FlightRecorder(ring_size=flight_ring, out_dir=flight_dir)
        tracer = Tracer([recorder])
        metrics = MetricsRegistry()
        install_gc_hook(metrics)
        return cls(metrics=metrics, tracer=tracer,
                   recorder=recorder, profiler=profiler)

    def trace(self, event: str, **fields) -> None:
        self.tracer.emit(event, **fields)

    def guard_dump(self, reason: str, **extra) -> Optional[str]:
        """Flight-recorder postmortem for an invariant violation (no-op
        without a recorder); returns the dump path."""
        if self.recorder is None:
            return None
        self.trace(GUARD, reason=reason, **extra)
        return self.recorder.dump(reason, registry=self.metrics,
                                  extra=extra or None)


__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "LATENCY_BUCKETS",
    "ListSink",
    "MetricsRegistry",
    "StepProfiler",
    "Telemetry",
    "Tracer",
    "load_jsonl",
    "log_buckets",
    "perfetto_export",
    "render_prometheus",
    "span",
    "write_perfetto",
]
