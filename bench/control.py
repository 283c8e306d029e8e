#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each seed, one run of the cell as the benchmark makes it (its window,
its sizes) and, on the same served requests or batches, the control: the
plain reference computed in bfloat16 in the program's place.  Prints one
JSON line per seed with the program's reading (``sound``) and the
control's (``control``) of the cell's compared number.  The benchmark's
own runs never run the control.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    try:
        harness.prepare_jax()
        for seed in a.seeds:
            got = {}

            def control(ref, params, c, served, cmp):
                got["sound"] = cmp
                got["control"] = ref.control(params, c, served)

            t0 = time.perf_counter()
            res = harness.run_cell(a.workload, seed, a.seconds, False, t0,
                                   hooks={"control": control})
            print(json.dumps({"workload": a.workload, "seed": seed,
                              "sound": got["sound"], "control": got["control"],
                              "metrics": res["metrics"],
                              "run_s": time.perf_counter() - t0}), flush=True)
    except harness.CellError as e:
        harness.log(f"cannot run: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
