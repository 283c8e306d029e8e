#!/usr/bin/env python3
"""Chip benchmark of the Xpikeformer serving engine: one cell per process.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix; the harness finds their files by name
(``bench/harness.py``).  The run builds the weights on the device from the
seed, warms up the cell's own shapes (set-up), measures for ``--seconds``
on the host clock, checks what the timed path produced against the plain
reference, and prints one JSON result as its last line of standard output.
``--trace 1`` runs the same load with a profiler window and reports the
per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        harness.prepare_jax()
        res = harness.run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                               T_START)
    except harness.CellError as e:
        harness.log(f"cannot run: {e}")
        return 1
    harness.emit(**res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
