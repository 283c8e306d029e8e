"""Kernel microbenchmarks.  The platform decides how the kernels run:
interpreted on CPU (a correctness-path timing that says nothing about the
chip), compiled on TPU.

    PYTHONPATH=src python benchmarks/kernels_micro.py --smoke
    PYTHONPATH=src python benchmarks/kernels_micro.py --smoke --json out.json

Two kinds of rows:

* **primitive kernels** — packed SSA attention, fused LIF, AIMC spiking
  linear: one ``pallas_call`` each, timed standalone.
* **decode layer step, fused vs unfused** — the same jitted serving
  ``decode_step`` (reduced spiking arch, pallas backend) run through both
  :class:`repro.kernels.plan.DecodePlan` strategies.  The fused plan
  launches ONE megakernel per decoder layer (Q/K/V, SSA decode,
  attention-out and FFN tail all inside the kernel, spike trains staying
  in VMEM); the unfused plan is the per-primitive
  path with an HBM round-trip between every stage.  Their ratio
  ``fused_vs_unfused_step`` (unfused us / fused us, higher = fused wins)
  is machine-robust — both legs run in the same process on the same
  runner — and is gated in ``benchmarks/baseline.json`` by
  ``check_regression.py``.

Timings are median-of-3 trials.  ``run(fast)`` rows integrate with
``benchmarks/run.py`` CSV output.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops

SPIKING_ARCH = "xpikeformer-gpt-4-256"


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _median3(fn, *args, **kw):
    return statistics.median(_time(fn, *args, reps=1, **kw) for _ in range(3))


def _primitive_rows():
    key = jax.random.PRNGKey(0)
    rows = []
    t, b, h, n, d = 2, 1, 2, 64, 32
    q = jax.random.bernoulli(key, 0.3, (t, b, h, n, d)).astype(jnp.uint8)
    us = _median3(ops.ssa_attention_packed, q, q, q, key, causal=False)
    rows.append(("kernels/ssa_attention_packed", us,
                 f"shape=T{t}B{b}H{h}N{n}D{d}"))

    cur = jax.random.normal(key, (8, 4096))
    us = _median3(ops.lif_fused, cur)
    rows.append(("kernels/lif_fused", us, "shape=8x4096"))

    sp = jax.random.bernoulli(key, 0.3, (4, 32, 256)).astype(jnp.float32)
    w = jax.random.randint(key, (256, 256), -15, 16, jnp.int8)
    sc = jnp.full((256,), 0.05, jnp.float32)
    us = _median3(ops.aimc_spiking_linear, sp, w, sc)
    rows.append(("kernels/aimc_spiking_linear", us, "shape=4x32x256->256"))
    return rows


def _decode_step_rows(smoke: bool = True, *, batch: int = 4,
                      cache_len: int = 32, steps: int = 4):
    """Fused vs unfused jitted serving decode step on the pallas backend.

    Per-step wall time over ``steps`` chained steps (identical shapes, one
    compile per plan), median of 3 trials."""
    from repro.configs.registry import get_config, reduced_config
    from repro.engine import PallasBackend
    from repro.kernels.plan import build_decode_plan
    from repro.models import transformer as T

    cfg = reduced_config(SPIKING_ARCH) if smoke else get_config(SPIKING_ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    backend = PallasBackend()
    seeds = jnp.arange(batch, dtype=jnp.uint32)
    tok = jnp.full((batch, 1), 5, jnp.int32)

    times = {}
    for kernel in ("unfused", "fused"):
        plan = build_decode_plan(cfg, backend, kernel=kernel)

        @jax.jit
        def step(cache, tok, plan=plan):
            return T.decode_step(params, cache, tok, cfg, backend=backend,
                                 seeds=seeds, plan=plan)

        _, cache = step(T.init_cache(cfg, batch, cache_len), tok)  # compile

        def chain(cache=cache, step=step):
            lo = None
            for _ in range(steps):
                lo, cache = step(cache, tok)
            return lo

        times[kernel] = _median3(chain) / steps
    rows = [(f"kernels/decode_step[{k}]", us,
             f"arch={SPIKING_ARCH} B={batch} L={cache_len} pallas")
            for k, us in times.items()]
    return rows, times["unfused"] / max(times["fused"], 1e-9)


def bench(smoke: bool = True):
    """Returns the {results, ratios} dict written to --json."""
    rows = _primitive_rows()
    step_rows, rel = _decode_step_rows(smoke)
    results = [{"name": name, "us_per_call": us, "detail": detail}
               for name, us, detail in rows + step_rows]
    return {
        "meta": {"smoke": smoke, "device": jax.devices()[0].platform},
        "results": results,
        "ratios": {"fused_vs_unfused_step": rel},
    }


def run(fast: bool = True):
    """benchmarks/run.py entry: (name, us_per_call, derived) rows."""
    rows = _primitive_rows()
    step_rows, rel = _decode_step_rows(fast)
    rows += step_rows
    rows.append(("kernels/ratio/fused_vs_unfused_step", 0.0, f"{rel:.2f}x"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", default=False,
                    help="reduced config for the decode-step rows (CPU CI)")
    ap.add_argument("--json", default=None, help="write results JSON here")
    a = ap.parse_args(argv)
    out = bench(smoke=a.smoke)
    for r in out["results"]:
        print(f"{r['name']:40s} {r['us_per_call']:12.1f} us  {r['detail']}")
    for k, v in out["ratios"].items():
        print(f"{'ratio/' + k:40s} {v:12.2f} x")
    if a.json:
        with open(a.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"[kernels_micro] wrote {a.json}")


if __name__ == "__main__":
    main()
