"""The readers of the program's spans and counters, on made-up values and
on the recorded 20-step chip trace (taken before the program had spans)."""

from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import program_spans as PS
import trace_reduce

DATA = Path(__file__).resolve().parent / "data" / "decode_20_steps.xplane.pb.gz"


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def stats(**fields):
    return SimpleNamespace(**fields)


COUNTERS = {"decode_steps": 0, "turnarounds": 0, "host_turnaround_s": 0.0,
            "pool_writes": 0, "page_cols_live": 0, "page_cols_visited": 0}


@pytest.mark.parametrize("name,after,want", [
    ("host_turnaround_ms.decode", {"turnarounds": 4, "host_turnaround_s": 0.04},
     10.0),
    ("host_turnaround_ms.serve", {"turnarounds": 2, "host_turnaround_s": 0.03},
     15.0),
    ("pool_writes_per_step.decode", {"decode_steps": 20, "pool_writes": 197},
     9.85),
    ("live_page_share.decode", {"page_cols_live": 1024,
                                "page_cols_visited": 4096}, 0.25),
])
def test_counter_readers(name, after, want):
    """Each reads the window's change of the scheduler's counters, and
    nothing where the program has no such counter or did no work."""
    st0 = stats(**COUNTERS)
    st = stats(**{**COUNTERS, **after})
    got = reader(name).read({"stats": st, "stats0": st0, "trace": None})
    assert got == pytest.approx(want)
    assert reader(name).read({"stats": st0, "stats0": st0}) is None
    bare = stats(decode_steps=20)  # a program without the counters
    assert reader(name).read({"stats": bare, "stats0": bare}) is None


def test_idle_split_among_innermost_spans():
    ns = 1e-9
    devices = [[(0.0, 60.0), (80.0, 20.0)]]  # idle [60, 80) and [100, 120)
    marks = {"bench.window_open": 0.0, "bench.window_close": 120.0}
    spans = [("xpk.pump", 50.0, 60.0), ("xpk.sched.step", 55.0, 20.0),
             ("xpk.gc", 70.0, 2.0),  # another thread, inside the step
             ("xpk.pump.admit", 0.0, 5.0),  # over no idle time
             ("xpk.pump.stream", 130.0, 5.0)]  # after the window
    idle = PS.idle_by_span_events(devices, spans, marks)
    assert idle == {"xpk.pump": pytest.approx(15 * ns),
                    "xpk.sched.step": pytest.approx(13 * ns),
                    "xpk.gc": pytest.approx(2 * ns),
                    "xpk.pump.admit": 0.0,
                    PS.UNATTRIBUTED: pytest.approx(10 * ns)}
    assert sum(idle.values()) == pytest.approx(40 * ns)


def test_recorded_trace_reads_all_idle_as_unattributed(monkeypatch, capsys):
    """The recorded trace has no ``xpk.*`` span: all of its device idle
    time is unattributed, and the metric reports nothing."""
    devices, spans, marks = PS.load(DATA)
    assert spans == [] and marks == {} and len(devices) == 1
    idle = PS.idle_by_span(DATA)
    assert set(idle) == {PS.UNATTRIBUTED}
    ops = [(s, s + d) for s, d in devices[0]]
    busy, _ = trace_reduce.union_length(ops)
    extent = max(e for _, e in ops) - min(s for s, _ in ops)
    assert idle[PS.UNATTRIBUTED] == pytest.approx((extent - busy) * 1e-9)
    assert idle[PS.UNATTRIBUTED] > 0.1

    monkeypatch.setattr(PS, "newest_trace", lambda: DATA)
    share = reader("idle_unattributed_share.decode")
    assert share.read({"trace": {}}) is None
    assert share.read({"trace": None}) is None
    assert PS.main([str(DATA)]) == 0
    assert "unattributed" in capsys.readouterr().out


def test_unattributed_share_reader(monkeypatch):
    monkeypatch.setattr(PS, "newest_trace", lambda: Path("t.xplane.pb"))
    monkeypatch.setattr(PS, "idle_by_span", lambda p: {
        "xpk.sched.commit": 0.06, "xpk.gc": 0.02, PS.UNATTRIBUTED: 0.02})
    assert reader("idle_unattributed_share.decode").read(
        {"trace": {}}) == pytest.approx(20.0)
    monkeypatch.setattr(PS, "newest_trace", lambda: None)
    assert reader("idle_unattributed_share.decode").read({"trace": {}}) is None
