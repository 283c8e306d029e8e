#!/usr/bin/env python3
"""Device idle time of a traced run, split among the program's own host
spans (``xpk.*``, ``repro.obs.spans``).

    python bench/program_spans.py [trace.xplane.pb[.gz]]

Without an argument it reads the newest ``*.xplane.pb`` under the
benchmark's trace directory: one process runs one cell, and its trace
window clears that directory first.  Inside the harness's
``bench.window_open`` / ``bench.window_close`` marks (the whole trace
where it has none), each gap between the device's operations is split by
overlap among the innermost ``xpk.*`` span open over each part of it (the
shortest, as ``trace_reduce`` labels gaps); the part under no span is
``unattributed``.  Seconds are averaged over the trace's devices.
"""

from __future__ import annotations

import gzip
import heapq
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

PREFIX = "xpk."
UNATTRIBUTED = "unattributed"
Span = Tuple[str, float, float]  # (name, start, duration), ns


def newest_trace() -> Optional[Path]:
    found = sorted((harness.OUT / "trace").glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def load(path: Path):
    """([device operations per device], [xpk spans], {window mark: start})."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    devices, spans, marks = [], [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append([(float(e.start_ns), float(e.duration_ns))
                            for ln in plane.lines
                            if ln.name == trace_reduce.OPS_LINE
                            for e in ln.events])
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
                    elif e.name in ("bench.window_open", "bench.window_close"):
                        marks[e.name] = float(e.start_ns)
    return devices, spans, marks


def _innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """Pieces of time under at least one span, each labelled with the
    shortest span open over it."""
    bounds = sorted({t for _, s, d in spans for t in (s, s + d)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    open_, pieces, i = [], [], 0  # open_: heap of (duration, end, name)
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            name, s, d = by_start[i]
            heapq.heappush(open_, (d, s + d, name))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        if open_:
            pieces.append((a, b, open_[0][2]))
    return pieces


def idle_by_span_events(devices: List[List[Tuple[float, float]]],
                        spans: List[Span],
                        marks: Dict[str, float]) -> Dict[str, float]:
    """Idle seconds per innermost ``xpk.*`` span, and ``unattributed``;
    every span name open in the window has an entry, zero or not."""
    ops = [(s, s + d) for dev in devices for s, d in dev]
    if len(marks) == 2:
        lo, hi = marks["bench.window_open"], marks["bench.window_close"]
    elif ops:
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    else:
        return {UNATTRIBUTED: 0.0}
    spans = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
             for n, s, d in spans if s < hi and s + d > lo]
    pieces = _innermost(spans)
    out: Dict[str, float] = defaultdict(float)
    for n, _, _ in spans:
        out[n] += 0.0
    out[UNATTRIBUTED] += 0.0
    n_dev = max(len(devices), 1)
    for dev in devices:
        clipped = [(max(s, lo), min(s + d, hi)) for s, d in dev
                   if s < hi and s + d > lo]
        _, merged = trace_reduce.union_length(clipped)
        edges = [[lo, lo]] + merged + [[hi, hi]]
        gaps = [(edges[k][1], edges[k + 1][0]) for k in range(len(edges) - 1)
                if edges[k + 1][0] > edges[k][1]]
        j = 0
        for g0, g1 in gaps:
            while j < len(pieces) and pieces[j][1] <= g0:
                j += 1
            covered = 0.0
            k = j
            while k < len(pieces) and pieces[k][0] < g1:
                ov = min(g1, pieces[k][1]) - max(g0, pieces[k][0])
                if ov > 0:
                    out[pieces[k][2]] += ov * 1e-9 / n_dev
                    covered += ov
                k += 1
            out[UNATTRIBUTED] += (g1 - g0 - covered) * 1e-9 / n_dev
    return dict(out)


def idle_by_span(path: Path) -> Dict[str, float]:
    """:func:`idle_by_span_events` of the trace file ``path``."""
    return idle_by_span_events(*load(path))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = Path(argv[0]) if argv else newest_trace()
    if path is None:
        print(f"no trace under {harness.OUT / 'trace'}", file=sys.stderr)
        return 1
    idle = idle_by_span(path)
    total = sum(idle.values())
    print(f"{path}: device idle {total:.6f} s")
    print(f"{'span':<24}{'idle_s':>12}{'share':>9}")
    for name, secs in sorted(idle.items(), key=lambda kv: -kv[1]):
        share = 100.0 * secs / total if total > 0 else 0.0
        print(f"{name:<24}{secs:>12.6f}{share:>8.2f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
