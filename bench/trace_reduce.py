"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, the self time of each device operation,
the time of each compiled program and each Mosaic kernel, the longest idle
gaps labelled with the host span open over them, and the ``breakdown`` of
the result line.

On a TPU the device planes are ``/device:TPU:<n>``.  Their ``XLA Modules``
line holds one event per program execution (``jit_step(<fingerprint>)``),
their ``XLA Ops`` line one event per operation, named by its HLO text
(``%fused_decode_layer_paged.9 = (...) custom-call(...)``); a ``while``
event holds the operations of its body, so operation times are counted as
self time.  Asynchronous copies (``Async XLA Ops``) are not counted as
busy.  Host spans are the ``bench.*`` annotations of the harness's own
files.  All times are nanoseconds on one clock.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")


def union_length(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, List]:
    """Length of the union of [start, end) intervals, and the merged ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_name(text: str) -> str:
    """``%fused_decode_layer_paged.9 = (...) custom-call(...)`` ->
    ``fused_decode_layer_paged``: the HLO instruction without its number."""
    head = text.split(" = ", 1)[0].lstrip("%").strip()
    return _SUFFIX.sub("", head)


def module_name(text: str) -> str:
    """``jit_step(6116197082165541810)`` -> ``jit_step``."""
    return text.split("(", 1)[0]


def is_kernel(text: str) -> bool:
    """A Mosaic (Pallas) kernel: a TPU custom call."""
    return "custom-call(" in text


def self_times(events: List[Tuple[str, float, float]]) -> List[float]:
    """Each event's duration minus the parts covered by events nested in it
    (events of one line nest or do not overlap)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack: List[int] = []
    for i in order:
        s = events[i][1]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= events[i][2]
        stack.append(i)
    return own


def load(path: Path):
    """([(plane, {line: [(name, start, dur)]})], [(host span, start, dur)])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                               for e in ln.events]
                     for ln in plane.lines if ln.name in (OPS_LINE, MODULES_LINE)}
            devices.append((plane.name, lines))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return devices, host


def _label(gap: Tuple[float, float], host) -> str:
    """The innermost host span open over the gap's middle, or ``none``."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, s, d in host:
        if s <= mid <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def _clip(events, lo: float, hi: float):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce(path: Path, window_s: float, top: int = 10) -> Dict:
    """Reduce the trace file (see :func:`reduce_events`)."""
    devices, host = load(path)
    return reduce_events(devices, host, window_s, top)


def reduce_events(devices, host, window_s: float, top: int = 10) -> Dict:
    """Reduce loaded events to the window between the harness's
    ``bench.window_open`` and ``bench.window_close`` marks (the whole trace,
    with the given ``window_s``, where it has no marks)."""
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    marks = {name: s for name, s, _ in host
             if name in ("bench.window_open", "bench.window_close")}
    lo = hi = None
    if len(marks) == 2:
        lo, hi = marks["bench.window_open"], marks["bench.window_close"]
        window_s = (hi - lo) * 1e-9
        devices = [(p, {ln: _clip(evs, lo, hi) for ln, evs in lines.items()})
                   for p, lines in devices]
    n = len(devices)
    busy = 0.0
    op_s = defaultdict(float)  # "module/op" -> self seconds
    kernel_s = defaultdict(float)  # "module/kernel" -> seconds
    module_s = defaultdict(float)
    module_runs = defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for _, lines in devices:
        ops = lines.get(OPS_LINE, [])
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, _, d in mods:
            module_s[module_name(name)] += d * 1e-9 / n
            module_runs[module_name(name)] += 1
        b, merged = union_length((s, s + d) for _, s, d in ops)
        busy += b * 1e-9 / n
        if lo is not None:  # the window's edges bound its first and last gap
            merged = [[lo, lo]] + merged + [[hi, hi]]
        gaps += [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)
                 if merged[i + 1][0] > merged[i][1]]
        for (text, s, d), own in zip(ops, self_times(ops)):
            k = bisect.bisect_right(starts, s) - 1
            mod = module_name(mods[k][0]) if k >= 0 and s < starts[k] + mods[k][2] \
                else "?"
            key = f"{mod}/{op_name(text)}"
            op_s[key] += own * 1e-9 / n
            if is_kernel(text):
                kernel_s[key] += d * 1e-9 / n
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_by_span = defaultdict(float)
    for g in gaps:
        idle_by_span[_label(g, host)] += (g[1] - g[0]) * 1e-9 / n
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy,
        "window_s": window_s,
        "devices": n,
        "op_s": dict(op_s),
        "kernel_s": dict(kernel_s),
        "module_s": dict(module_s),
        "module_runs": dict(module_runs),
        "idle_by_host_span_s": dict(idle_by_span),
        "breakdown": {
            "device_ops": [[k, v] for k, v in ranked],
            "idle_gaps": [[_label(g, host), (g[1] - g[0]) * 1e-9]
                          for g in gaps[:top]],
        },
    }


def kernel_seconds(red: Dict, kernel: str) -> float:
    """Device seconds of the Mosaic kernel named ``kernel``, any program."""
    return sum(v for k, v in red["kernel_s"].items()
               if k.split("/", 1)[1] == kernel)


def module_seconds(red: Dict, module: str) -> Tuple[float, int]:
    """(device seconds, executions) of the compiled program ``module``."""
    return red["module_s"].get(module, 0.0), red["module_runs"].get(module, 0)
