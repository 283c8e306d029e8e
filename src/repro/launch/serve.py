"""Serving launcher: continuous-batching inference over any --arch.

    python -m repro.launch.serve --arch yi-9b --requests 8
    python -m repro.launch.serve --arch xpikeformer-gpt-4-256 --backend pallas
    python -m repro.launch.serve --arch xpikeformer-gpt-4-256 --paged \\
        --page-len 8                            # paged spike-train KV cache
    python -m repro.launch.serve --arch xpikeformer-gpt-4-256 --program \\
        --drift-step 60 --recal-every 3600      # PCM lifecycle + energy
    python -m repro.launch.serve --arch xpikeformer-gpt-4-256 --http \\
        --port 8000                             # HTTP/SSE front door
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    python -m repro.launch.serve --arch xpikeformer-gpt-4-256 \\
        --backend pallas --mesh 2x4             # (data, model) mesh serving

Thin CLI over the ``repro.serving`` subsystem: a :class:`~repro.serving.
BatchScheduler` splices requests into free slots mid-flight (continuous
batching), keeps per-slot state in a :class:`~repro.serving.DecodeState`
pytree, and advances every slot with one jit-compiled batched
``decode_step``.  Spiking SSA archs decode through the engine's pluggable
backend (reference / integer / pallas) over spike-train KV caches; all
other archs use the conventional float KV / recurrent-state path.  Greedy
sampling.

``--mesh DATAxMODEL`` places the whole stack on a (data, model) mesh via
:class:`repro.distributed.Executor`: decode slots are data-parallel,
spiking linears / SSA attention run tensor-parallel over ``model``
(bit-exact vs single-device on the integer/pallas backends).

``--program`` programs the spiking-linear weights onto simulated PCM
(:mod:`repro.aimc_device`) before serving; ``--drift-step`` /
``--recal-every`` set the device-seconds-per-decode-step and GDC
recalibration interval of the drift lifecycle (0 = wall clock / never).
Per-request energy (measured spike events x Table-II op energies) prints
with the serve summary.

``--http`` runs the :mod:`repro.server` front door instead of synthetic
requests: ``POST /generate`` streams tokens over SSE through the same
scheduler (admission control, per-tenant energy budgets, ``GET /stats``,
``GET /metrics`` Prometheus exposition), until Ctrl-C; the serve summary
(tok/s, J/token) prints on shutdown.

Observability (:mod:`repro.obs`): ``--trace-out events.jsonl`` appends
request-lifecycle trace events (submit/admit/prefill-chunk/first-token/
decode/preempt/finish, one JSON object per line — convert with
``repro.obs.perfetto_export`` for ``ui.perfetto.dev``);
``--profile-steps N`` captures N decode steps with ``jax.profiler`` into
``--profile-dir``.  Both are opt-in and host-side only: telemetry never
changes a decoded token, a booked joule, or the compile count.
"""

from __future__ import annotations

import argparse
import time
from typing import List

import jax
import jax.numpy as jnp

from repro import aimc_device as AD
from repro.compile_cache import use_compile_cache
from repro.configs.base import ParallelConfig
from repro.configs.registry import get_config, reduced_config
from repro.engine import get_backend
from repro.launch.mesh import make_serving_mesh, make_test_mesh, parse_mesh_spec
from repro.models import transformer as T
from repro.obs import JsonlSink, StepProfiler, Telemetry
from repro.parallel import sharding as SH
from repro.serving import BatchScheduler


def serve(
    arch: str,
    *,
    smoke: bool = True,
    n_requests: int = 8,
    slots: int = 4,
    max_new: int = 16,
    cache_len: int = 64,
    seed: int = 0,
    backend: str = "reference",
    program: bool = False,
    drift_step_s: float = 0.0,
    recal_every_s: float = 0.0,
    mesh_spec: str = "",
    paged: bool = False,
    page_len: int = 8,
    n_pages: int = 0,
    decode_kernel: str = "auto",
    http: bool = False,
    host: str = "127.0.0.1",
    port: int = 8000,
    trace_out: str = "",
    profile_steps: int = 0,
    profile_dir: str = "/tmp/xpike-profile",
):
    """Serve ``n_requests`` synthetic prompts; returns their outputs in
    submission order (continuous batching: a finished slot is refilled from
    the queue without draining the batch) and the scheduler that served
    them (its stats, device state and compiled step)."""
    cfg = reduced_config(arch) if smoke else get_config(arch)
    if cfg.frontend != "none":
        print(f"[serve] {arch} is a {cfg.family} backbone; serving over stub embeddings")
    if cfg.spiking and cfg.attention_kind == "ssa":
        print(f"[serve] {arch} decodes through the '{backend}' backend over "
              "spike-train KV caches (SSA serving path)")
    params = T.init_params(jax.random.PRNGKey(seed), cfg)

    drift = None
    if program:
        if not (cfg.spiking and cfg.attention_kind == "ssa"):
            raise SystemExit(f"--program needs a spiking SSA arch, not {arch}")
        params = AD.program_lm_tree(jax.random.PRNGKey(seed + 42), params,
                                    AD.AIMCConfig())
        drift = AD.DriftPolicy(seconds_per_step=drift_step_s,
                               recal_interval_s=recal_every_s)
        print(f"[serve] programmed spiking linears onto PCM "
              f"(drift {drift_step_s or 'wall-clock'} s/step, "
              f"GDC every {recal_every_s or 'never'} s)")

    paged_kw = dict(paged=paged, page_len=page_len,
                    n_pages=n_pages or None, decode_kernel=decode_kernel)
    if paged:
        print(f"[serve] paged spike-train KV cache: page_len={page_len}, "
              f"pool={n_pages or slots * (cache_len // page_len) + 2} pages, "
              "exact prefix sharing + chunked prefill")
    if mesh_spec:
        from repro.distributed import Executor

        shape = parse_mesh_spec(mesh_spec)
        mesh = make_serving_mesh(shape)
        ex = Executor(params, cfg, get_backend(backend), mesh)
        sch = ex.scheduler(slots=slots, cache_len=cache_len, drift=drift,
                           **paged_kw)
        print(f"[serve] mesh (data={shape[0]}, model={shape[1]}): "
              f"slots data-parallel, spiking kernels tensor-parallel "
              f"(TP {'on' if ex.plan.tp > 1 else 'off'})")
    else:
        mesh = make_test_mesh((1, 1))
        parallel = ParallelConfig(moe_impl="ep_a2a" if cfg.is_moe else "dense")
        pctx = SH.make_pctx(mesh, parallel)
        sch = BatchScheduler(
            params, cfg, get_backend(backend), slots=slots, cache_len=cache_len,
            pctx=pctx, moe_impl=parallel.moe_impl, drift=drift, **paged_kw,
        )
    if sch.plan is not None:
        print(f"[serve] decode kernel: {sch.plan.describe()}")

    # telemetry bundle: metrics registry + tracer + flight recorder, plus
    # the opt-in JSONL trace sink and jax.profiler window.  Host-side only
    # — attaching it never recompiles or changes a token.
    profiler = None
    if profile_steps > 0:
        profiler = StepProfiler(profile_steps, profile_dir)
        print(f"[serve] profiling {profile_steps} decode steps -> "
              f"{profile_dir} (jax.profiler)")
    obs = Telemetry.create(profiler=profiler)
    if trace_out:
        obs.tracer.add_sink(JsonlSink(trace_out))
        print(f"[serve] tracing request lifecycle -> {trace_out} (JSONL)")
    sch.attach_obs(obs)
    if http:
        _serve_http(sch, host=host, port=port)
        return [], sch
    rng = jax.random.PRNGKey(seed + 1)
    prompts: List[jnp.ndarray] = [
        jax.random.randint(jax.random.fold_in(rng, i), (int(4 + 3 * (i % 4)),), 0,
                           cfg.vocab_size, jnp.int32)
        for i in range(n_requests)
    ]
    rids = [sch.submit(p, max_new, seed=seed + i) for i, p in enumerate(prompts)]
    t0 = time.perf_counter()  # duration: monotonic, not wall clock
    outs = sch.run()
    dt = time.perf_counter() - t0
    st = sch.stats
    print(f"[serve] served {st.requests} requests, {st.decoded_tokens} tokens "
          f"in {dt:.2f}s ({st.tokens_per_sec:.1f} tok/s, "
          f"{st.decode_steps} batched decode steps, {st.admissions} admissions)")
    if paged:
        print(f"[serve] pages: peak {st.pages_in_use_peak} in use, "
              f"{st.prefix_hits} prefix hits ({st.prefix_hit_tokens} prompt "
              f"tokens reused), {st.cow_copies} copy-on-writes, "
              f"peak {st.peak_active_slots} concurrent slots")
    if st.energy_j > 0:
        print(f"[serve] energy: {st.energy_j*1e6:.2f} uJ total "
              f"({st.j_per_token*1e9:.1f} nJ/token, "
              f"{st.spike_events:.0f} spike events)")
        worst = max(sch.request_energy_j.items(), key=lambda kv: kv[1])
        print(f"[serve] per-request energy: max rid={worst[0]} "
              f"{worst[1]*1e9:.1f} nJ")
    if program:
        print(f"[serve] device clock t={st.t_device_s:.1f}s, "
              f"{st.recalibrations} GDC recalibrations")
    return [outs[r] for r in rids], sch


def _serve_http(sch: BatchScheduler, *, host: str, port: int) -> None:
    """Run the async HTTP/SSE front door over an already-built scheduler
    until interrupted, then print the usual serve summary."""
    import asyncio

    from repro.server import FrontDoor, HttpFrontDoor

    async def _run():
        srv = HttpFrontDoor(FrontDoor(sch), host=host, port=port)
        await srv.start()
        print(f"[serve] HTTP front door on http://{srv.host}:{srv.port} "
              "(POST /generate, GET /stats, GET /metrics, GET /healthz); "
              "Ctrl-C to stop", flush=True)
        try:
            await srv._server.serve_forever()
        except asyncio.CancelledError:
            pass
        finally:
            await srv.stop()

    t0 = time.perf_counter()  # duration: monotonic, not wall clock
    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        pass
    st = sch.stats
    st.wall_s += time.perf_counter() - t0
    print(f"\n[serve] served {st.requests} requests, {st.decoded_tokens} "
          f"tokens ({st.tokens_per_sec:.1f} tok/s, {st.decode_steps} batched "
          f"decode steps, {st.admissions} admissions)")
    if st.energy_j > 0:
        print(f"[serve] energy: {st.energy_j*1e6:.2f} uJ total "
              f"({st.j_per_token*1e9:.1f} nJ/token, "
              f"{st.spike_events:.0f} spike events)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "integer", "pallas"])
    ap.add_argument("--mesh", default="",
                    help="serve on a (data, model) mesh, e.g. 2x4 or 4 "
                         "(data-parallel only); needs data*model devices")
    ap.add_argument("--paged", action="store_true", default=False,
                    help="block-paged spike-train KV cache (spiking SSA "
                         "archs): exact prefix sharing + chunked prefill")
    ap.add_argument("--page-len", type=int, default=8,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--pages", type=int, default=0,
                    help="physical page-pool size (--paged; 0 = slots x "
                         "cache_len / page_len + reserved)")
    ap.add_argument("--decode-kernel", default="auto",
                    choices=["auto", "fused", "unfused"],
                    help="decode kernel strategy: 'fused' = one megakernel "
                         "launch per decoder layer (spiking SSA attention "
                         "stacks on the integer/pallas backends); 'auto' "
                         "picks fused where supported")
    ap.add_argument("--http", action="store_true", default=False,
                    help="serve over HTTP/SSE (POST /generate streams "
                         "tokens) instead of running synthetic requests")
    ap.add_argument("--trace-out", default="",
                    help="append request-lifecycle trace events to this "
                         "JSONL file (repro.obs; perfetto_export converts "
                         "it for ui.perfetto.dev)")
    ap.add_argument("--profile-steps", type=int, default=0,
                    help="capture this many decode steps with jax.profiler "
                         "(0 = off)")
    ap.add_argument("--profile-dir", default="/tmp/xpike-profile",
                    help="jax.profiler trace output directory "
                         "(--profile-steps)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--full", dest="smoke", action="store_false", default=True)
    ap.add_argument("--program", action="store_true", default=False,
                    help="program spiking linears onto simulated PCM first")
    ap.add_argument("--drift-step", type=float, default=0.0,
                    help="device seconds per decode step (0 = wall clock)")
    ap.add_argument("--recal-every", type=float, default=0.0,
                    help="GDC recalibration interval in device seconds (0 = never)")
    a = ap.parse_args(argv)
    use_compile_cache()
    serve(a.arch, smoke=a.smoke, n_requests=a.requests, slots=a.slots,
          max_new=a.max_new, cache_len=a.cache_len, backend=a.backend,
          program=a.program, drift_step_s=a.drift_step,
          recal_every_s=a.recal_every, mesh_spec=a.mesh, paged=a.paged,
          page_len=a.page_len, n_pages=a.pages, decode_kernel=a.decode_kernel,
          http=a.http, host=a.host, port=a.port, trace_out=a.trace_out,
          profile_steps=a.profile_steps, profile_dir=a.profile_dir)


if __name__ == "__main__":
    main()
