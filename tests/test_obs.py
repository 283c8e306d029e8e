"""Observability layer (:mod:`repro.obs`): metrics registry, Prometheus
exposition, lifecycle tracing, flight recorder, profiler hooks.

Two invariants anchor everything here:

* **no perturbation** — a scheduler run with the full telemetry bundle
  attached streams the same tokens and books the same joules as a
  telemetry-off run, and the jitted decode_step still compiles exactly
  once (telemetry is host-side bookkeeping, never jitted code);
* **single source of truth** — the registry's counters mirror
  :class:`~repro.serving.ServeStats` by delta, so ``/stats``,
  ``/metrics`` and the scheduler's own stats can never disagree.

The scheduler-facing tests run on the CI backend matrix
(``engine_backend``); the registry/tracer/recorder units are pure host
Python and run once.
"""

import json
import re

import jax
import pytest

from repro.configs.registry import reduced_config
from repro.engine import get_backend
from repro.models import transformer as T
from repro.obs import (
    LATENCY_BUCKETS,
    JsonlSink,
    ListSink,
    MetricsRegistry,
    StepProfiler,
    Telemetry,
    Tracer,
    load_jsonl,
    log_buckets,
    perfetto_export,
    render_prometheus,
)
from repro.obs import trace as TR
from repro.server import FrontDoor
from repro.serving import BatchScheduler

SPIKING = "xpikeformer-gpt-4-256"


@pytest.fixture(scope="module")
def spiking_setup():
    cfg = reduced_config(SPIKING)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# -- metrics registry -----------------------------------------------------


def test_log_buckets_geometry():
    b = log_buckets(1e-6, 100.0, per_decade=3)
    assert b[0] == pytest.approx(1e-6)
    assert b[-1] >= 100.0
    ratios = [hi / lo for lo, hi in zip(b, b[1:])]
    for r in ratios:  # constant geometric step: 10^(1/3)
        assert r == pytest.approx(10.0 ** (1 / 3))
    assert b == LATENCY_BUCKETS
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_buckets(1.0, 1.0)


def test_histogram_bucket_counts():
    """Observations land in the right (upper-inclusive) bucket; the last
    entry is the +Inf bucket."""
    reg = MetricsRegistry(namespace="")
    h = reg.histogram("lat", "t", bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 10.0, 50.0, 1000.0):
        h.observe(v)
    # le=1: {0.5, 1.0}; le=10: {5, 10}; le=100: {50}; +Inf: {1000}
    assert h.bucket_counts() == [2, 2, 1, 1]
    snap = h.snapshot()
    assert snap["count"] == 6 and snap["sum"] == pytest.approx(1066.5)
    assert snap["bounds"] == [1.0, 10.0, 100.0]
    # labeled series are independent
    h2 = reg.histogram("lab", "t", ("k",), bounds=(1.0,))
    h2.observe(0.5, "a")
    h2.observe(2.0, "b")
    assert h2.bucket_counts("a") == [1, 0]
    assert h2.bucket_counts("b") == [0, 1]
    assert h2.bucket_counts("never") == [0, 0]


def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "r", ("outcome",))
    c.inc(1.0, "ok")
    c.inc(2.0, "ok")
    c.inc(1.0, "err")
    assert c.value("ok") == 3.0 and c.value("err") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1.0, "ok")  # counters are monotone
    with pytest.raises(ValueError):
        c.inc(1.0)  # label arity enforced
    g = reg.gauge("depth", "d")
    g.set(5)
    g.dec(2)
    assert g.value() == 3.0
    # get-or-create: same object back, mismatis rejected
    assert reg.counter("reqs_total", "r", ("outcome",)) is c
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("reqs_total", "r", ("other",))  # label mismatch
    assert reg.get("xpike_reqs_total") is c  # namespaced lookup


def test_render_prometheus_golden():
    """Exact exposition text for a small registry (format 0.0.4)."""
    reg = MetricsRegistry(namespace="t")
    c = reg.counter("reqs_total", "requests", ("outcome",))
    c.inc(3, "ok")
    c.inc(1, "err")
    reg.gauge("depth", "queue depth").set(2)
    h = reg.histogram("lat_seconds", "latency", bounds=(0.125, 1.0))
    for v in (0.0625, 0.5, 5.0):  # exact binary floats: stable reprs
        h.observe(v)
    assert render_prometheus(reg) == (
        "# HELP t_depth queue depth\n"
        "# TYPE t_depth gauge\n"
        "t_depth 2\n"
        "# HELP t_lat_seconds latency\n"
        "# TYPE t_lat_seconds histogram\n"
        't_lat_seconds_bucket{le="0.125"} 1\n'
        't_lat_seconds_bucket{le="1"} 2\n'
        't_lat_seconds_bucket{le="+Inf"} 3\n'
        "t_lat_seconds_sum 5.5625\n"
        "t_lat_seconds_count 3\n"
        "# HELP t_reqs_total requests\n"
        "# TYPE t_reqs_total counter\n"
        't_reqs_total{outcome="ok"} 3\n'
        't_reqs_total{outcome="err"} 1\n'
    )


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"  # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'  # labels
    r" -?(\d+(\.\d+)?([eE][+-]?\d+)?|inf|nan)$", re.IGNORECASE)


def assert_prometheus_well_formed(text: str) -> None:
    """Every line is a HELP/TYPE comment or a well-formed sample; every
    histogram's cumulative buckets are nondecreasing and end at _count."""
    assert text.endswith("\n")
    buckets = {}
    counts = {}
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"malformed sample line: {line!r}"
        name = line.split("{")[0].split(" ")[0]
        value = float(line.rsplit(" ", 1)[1])
        if "_bucket{" in line:
            series = line.rsplit(" ", 1)[0]
            key = (name, re.sub(r'le="[^"]*",?', "", series))
            buckets.setdefault(key, []).append(value)
        elif name.endswith("_count"):
            counts[line.rsplit(" ", 1)[0]] = value
    for (name, _series_key), cum in buckets.items():
        assert cum == sorted(cum), f"{name}: buckets not cumulative"
        # the +Inf bucket must equal the series _count
        base = name[:-len("_bucket")]
        matching = [v for k, v in counts.items() if k.startswith(base)]
        assert cum[-1] in matching, f"{name}: +Inf bucket != _count"


def test_label_escaping():
    reg = MetricsRegistry(namespace="")
    c = reg.counter("odd_total", "h", ("who",))
    c.inc(1.0, 'quo"te\\back\nline')
    text = render_prometheus(reg)
    assert r'who="quo\"te\\back\nline"' in text
    assert_prometheus_well_formed(text)


# -- tracer / sinks -------------------------------------------------------


def test_tracer_noop_without_sinks():
    tr = Tracer()
    assert not tr.active
    tr.emit(TR.SUBMIT, rid=1)  # must not raise, must not allocate a sink
    sink = ListSink()
    tr.add_sink(sink)
    tr.emit(TR.ADMIT, rid=1, slot=0)
    assert tr.active and len(sink.events) == 1
    ev = sink.events[0]
    assert ev["event"] == TR.ADMIT and ev["rid"] == 1
    assert "ts" in ev and "mono" in ev
    tr.remove_sink(sink)
    tr.emit(TR.FINISH, rid=1)
    assert len(sink.events) == 1


def test_jsonl_sink_roundtrip(tmp_path):
    import numpy as np

    path = str(tmp_path / "trace.jsonl")
    sink = JsonlSink(path)
    tr = Tracer([sink])
    tr.emit(TR.SUBMIT, rid=0, prompt_len=4)
    tr.emit(TR.DECODE, rid=0, token=np.int32(7))  # numpy -> jsonable
    sink.close()
    evs = load_jsonl(path)
    assert [e["event"] for e in evs] == [TR.SUBMIT, TR.DECODE]
    assert evs[1]["token"] == 7.0
    assert evs[0]["mono"] <= evs[1]["mono"]


def test_perfetto_export_spans():
    sink = ListSink()
    tr = Tracer([sink])
    tr.emit(TR.SUBMIT, rid=0)
    tr.emit(TR.ADMIT, rid=0, slot=1)
    tr.emit(TR.FIRST_TOKEN, rid=0, token=5)
    tr.emit(TR.FINISH, rid=0)
    tr.emit(TR.GDC_RECAL, n=4)  # no rid: lands on track 0
    out = perfetto_export(sink.events)
    assert out["displayTimeUnit"] == "ms"
    evs = out["traceEvents"]
    spans = [(e["name"], e["tid"]) for e in evs if e["ph"] == "X"]
    assert ("queued", 0) in spans  # submit -> admit
    assert ("running", 0) in spans  # admit -> finish
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(instants) == 5
    for e in evs:
        assert e["ts"] >= 0.0
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
    assert perfetto_export([]) == {"traceEvents": [],
                                   "displayTimeUnit": "ms"}
    # a dangling phase (no finish) is closed at trace end
    dangling = perfetto_export(sink.events[:2])
    assert any(e["ph"] == "X" and e["name"] == "running"
               for e in dangling["traceEvents"])


def test_step_profiler_window(monkeypatch):
    """start_trace fires at step ``skip``, stop after ``steps`` more."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    p = StepProfiler(2, "/tmp/prof", skip=1)
    p.tick()  # step 0: skipped (compile step)
    assert not p.tracing and calls == []
    p.tick()  # step 1: capture starts
    assert calls == [("start", "/tmp/prof")] and p.tracing
    p.tick()  # step 2: window [1, 3) complete -> stop
    assert calls[-1][0] == "stop" and p.done
    p.tick()  # further ticks are no-ops
    assert len(calls) == 2
    with pytest.raises(ValueError):
        StepProfiler(0, "/tmp/prof")

    def refuse(d):
        raise RuntimeError("no profiler backend")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    q = StepProfiler(1, "/tmp/prof", skip=0)
    with pytest.raises(RuntimeError):  # a requested capture fails the run
        q.tick()


# -- scheduler integration (backend matrix) -------------------------------


def _jobs():
    return [(list(range(3 + i, 9 + i)), 4, 70 + i) for i in range(3)]


def _run(sch, jobs):
    sch.reset()
    rids = [sch.submit(p, mn, seed=s) for p, mn, s in jobs]
    outs = sch.run()
    return [list(outs[r]) for r in rids]


@pytest.fixture(scope="module")
def paged_sched(spiking_setup, engine_backend):
    cfg, params = spiking_setup
    return BatchScheduler(params, cfg, get_backend(engine_backend),
                          slots=2, cache_len=32, paged=True, page_len=8,
                          n_pages=12)


def test_telemetry_bitexact_and_compile_once(paged_sched, tmp_path):
    """Attaching the full bundle changes no token, no joule, no compile."""
    sch = paged_sched
    jobs = _jobs()
    want = _run(sch, jobs)  # telemetry-off baseline (also jit warmup)
    base_energy = sch.stats.energy_j
    base_spikes = sch.stats.spike_events

    sink = ListSink()
    obs = Telemetry.create(flight_dir=str(tmp_path))
    obs.tracer.add_sink(sink)
    sch.attach_obs(obs)
    got = _run(sch, jobs)
    assert got == want  # bit-exact token streams
    assert sch.stats.energy_j == base_energy  # bit-exact energy
    assert sch.stats.spike_events == base_spikes
    assert sch._decode._cache_size() == 1, \
        "attaching telemetry recompiled the decode step"

    # lifecycle trace covers the whole request arc
    names = {e["event"] for e in sink.events}
    assert {TR.SUBMIT, TR.ADMIT, TR.PREFILL_CHUNK, TR.FIRST_TOKEN,
            TR.DECODE, TR.FINISH, TR.EVICT} <= names
    finishes = [e for e in sink.events if e["event"] == TR.FINISH]
    assert {e["rid"] for e in finishes} == {e["rid"] for e in sink.events
                                            if e["event"] == TR.SUBMIT}


def test_counters_mirror_serve_stats(paged_sched):
    """Registry counters == ServeStats after a run, and stay lifetime-
    monotone across reset() while ServeStats rebases."""
    sch = paged_sched
    if sch.obs is None:
        sch.attach_obs(Telemetry.create())
    jobs = _jobs()
    _run(sch, jobs)
    m = sch.obs.metrics
    marks = {}
    for field, name, _help in BatchScheduler._STAT_COUNTERS:
        counter = m.get(f"xpike_{name}")
        assert counter is not None, name
        marks[name] = counter.value()
    st = sch.stats
    # first run since the counters existed may have prior totals; compare
    # deltas over one more run instead of absolutes
    _run(sch, jobs)
    st2 = sch.stats
    for field, name, _help in BatchScheduler._STAT_COUNTERS:
        delta = m.get(f"xpike_{name}").value() - marks[name]
        assert delta == pytest.approx(float(getattr(st2, field))), \
            f"counter {name} does not mirror ServeStats.{field}"
    # gauges reflect the drained server
    assert m.get("xpike_active_slots").value() == 0
    assert m.get("xpike_scheduler_queue_depth").value() == 0
    # exposition of the live registry is well-formed Prometheus text
    assert_prometheus_well_formed(render_prometheus(m))


def test_frontdoor_stats_nests_registry(paged_sched):
    """GET /stats ``metrics`` block == registry snapshot == ServeStats."""
    import asyncio

    sch = paged_sched
    jobs = _jobs()
    want = _run(sch, jobs)

    async def go():
        front = FrontDoor(sch)
        await front.start()
        try:
            streams = [await front.submit(p, mn, seed=s)
                       for p, mn, s in jobs]
            got = [await ts.tokens() for ts in streams]
            return front, got
        finally:
            await front.stop()

    sch.reset()
    front, got = asyncio.run(go())
    assert got == want  # telemetry-on front door stays bit-exact
    stats = front.stats_dict()
    assert json.loads(json.dumps(stats)) == stats  # JSON-serializable
    snap = stats["metrics"]
    assert snap == front.obs.metrics.snapshot()
    decoded = snap["xpike_decoded_tokens_total"]
    assert decoded["kind"] == "counter"
    # lifetime counter >= this run's ServeStats (earlier runs accumulate)
    assert decoded["values"] >= stats["scheduler"]["decoded_tokens"]
    assert "xpike_ttft_seconds" in snap
    assert snap["xpike_frontdoor_requests_total"]["values"]["completed"] \
        >= len(jobs)
    admits = snap["xpike_admission_decisions_total"]["values"]
    assert any(k.startswith("admit") for k in admits)


def test_flight_recorder_dumps_on_pool_guard(spiking_setup, tmp_path):
    """A PagePool double-free still raises, and the armed recorder writes
    a postmortem first (events + metrics snapshot + reason)."""
    cfg, params = spiking_setup
    sch = BatchScheduler(params, cfg, get_backend("reference"),
                         slots=2, cache_len=32, paged=True, page_len=8,
                         n_pages=12)
    obs = Telemetry.create(flight_dir=str(tmp_path))
    sch.attach_obs(obs)
    sch.submit(list(range(3, 9)), 2, seed=1)  # traces feed the ring

    pid = sch.pages.alloc()
    sch.pages.release(pid)  # refcount 1 -> 0: page freed
    with pytest.raises(ValueError, match="double free"):
        sch.pages.release(pid)
    assert len(obs.recorder.dumps) == 1
    dump = json.load(open(obs.recorder.dumps[0]))
    assert "double free" in dump["reason"]
    assert any(e["event"] == TR.SUBMIT for e in dump["events"])
    assert "xpike_decode_steps_total" in dump["metrics"]

    with pytest.raises(ValueError, match="use-after-free"):
        sch.pages.retain(pid)
    with pytest.raises(ValueError, match="unoccupied"):
        sch.evict(1)  # slot 1 never held a request
    assert len(obs.recorder.dumps) == 3  # one postmortem per guard hit
    assert len(set(obs.recorder.dumps)) == 3  # fresh file each time


def test_flight_recorder_per_slot_rings():
    from repro.obs import FlightRecorder

    rec = FlightRecorder(ring_size=4, per_slot=2)
    for i in range(10):
        rec.record({"event": TR.DECODE, "slot": i % 2, "step": i})
    assert len(rec.events()) == 4  # global ring bounded
    assert [e["step"] for e in rec.events(slot=0)] == [6, 8]
    assert [e["step"] for e in rec.events(slot=1)] == [7, 9]
    assert rec.events(slot=5) == []


def test_j_per_token_zero_token_convention(spiking_setup):
    """The documented denominator convention: 0 when nothing decoded and
    nothing booked; astronomically large (not a crash, not 0) when energy
    was booked before any token landed."""
    from repro.serving import ServeStats

    st = ServeStats()
    assert st.j_per_token == 0.0
    st.energy_j = 1e-6
    assert st.j_per_token == pytest.approx(1e-6 / 1e-9)
    st.decoded_tokens = 4
    assert st.j_per_token == pytest.approx(1e-6 / 4)
