"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run          # fast mode
    PYTHONPATH=src python -m benchmarks.run --full   # paper-scale training

Each module's ``run(fast)`` returns rows of (name, us_per_call, derived);
printed as ``name,us_per_call,derived`` CSV.
"""

from __future__ import annotations

import sys
import traceback


MODULES = [
    "benchmarks.ssa_convergence",
    "benchmarks.fig8_energy",
    "benchmarks.fig9_breakdown",
    "benchmarks.fig10_latency",
    "benchmarks.table6_sota",
    "benchmarks.kernels_micro",
    "benchmarks.backend_forward",
    "benchmarks.aimc_forward",
    "benchmarks.serving_throughput",
    "benchmarks.serving_load",
    "benchmarks.roofline",
    "benchmarks.table4_icl_ber",
    "benchmarks.table3_image_cls",
    "benchmarks.table5_drift",
]


def main() -> None:
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    fast = "--full" not in sys.argv
    only = [a for a in sys.argv[1:] if not a.startswith("--")]
    print("name,us_per_call,derived")
    failures = 0
    for modname in MODULES:
        if only and not any(o in modname for o in only):
            continue
        try:
            mod = __import__(modname, fromlist=["run"])
            for name, us, derived in mod.run(fast=fast):
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # report but keep the suite going
            failures += 1
            print(f"{modname},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
