#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the highest rate
the server sustains without a growing backlog.

    python bench/knee.py --workload gpt-8-512.serve --seconds 30 --rates 4 6 8 10

Runs the cell as the benchmark does (its set-up, its mix) at each rate
and prints, per rate, the requests due, the p50/p95 time to first token of
the requests due in the first and the last third of the window, and the
backlog (requests due and not yet given a slot) at the end of each third.
A backlog that grows from third to third means the rate is past the knee.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    harness.prepare_jax()
    for rate in a.rates:
        got = {}

        def mix(m, rate=rate):
            m = copy.deepcopy(m)
            m["arrivals"]["rate_rps"] = rate
            return m

        def served(samples):
            return samples[:1]  # the knee needs no full comparison

        t0 = time.perf_counter()
        try:
            res = harness.run_cell(a.workload, a.seed, a.seconds, False, t0,
                                   hooks={"mix": mix, "served": served,
                                          "records": got})
        except harness.CellError as e:
            harness.log(f"cannot run: {e}")
            return 1
        recs, t_0, end = got["recs"], got["t0"], got["end"]
        third = (end - t_0) / 3
        row = {"rate_rps": rate, "due": int(sum(r.due < end for r in recs))}
        for part, lo, hi in (("first", t_0, t_0 + third), ("last", end - third, end)):
            ttft = [(r.times[0] if r.times and r.times[0] <= end else end) - r.due
                    for r in recs if lo <= r.due < hi]
            if ttft:
                row[f"ttft_p50_ms_{part}"] = 1e3 * stats.percentile(ttft, 50)
                row[f"ttft_p95_ms_{part}"] = 1e3 * stats.percentile(ttft, 95)
        slot_at = {ev["rid"]: ev["mono"] for ev in got["admits"] if "slot" in ev}
        rid_of = {ev["fid"]: ev["rid"] for ev in got["admits"]
                  if "fid" in ev and "rid" in ev}
        admitted = [slot_at.get(rid_of.get(r.fid), float("inf")) for r in recs]
        row["backlog"] = [sum(1 for r, at in zip(recs, admitted)
                              if r.due <= t < at)
                          for t in (t_0 + third, t_0 + 2 * third, end)]
        row["waiting_at_end"] = sum(1 for r in recs if r.due < end and not r.times)
        row["metrics"] = res["metrics"]
        print(json.dumps(row, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
