"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the real ``train_step`` / ``prefill`` / ``serve_step`` with
     abstract (ShapeDtypeStruct) params, optimizer state, batch and cache —
     no device allocation,
  3. compiles it (SPMD partitioning for 256/512 devices),
  4. records ``memory_analysis()`` (proves it fits 16 GB/chip HBM),
     ``cost_analysis()`` (FLOPs/bytes for the roofline), and the collective
     bytes parsed from the post-partitioning HLO,
  5. writes a JSON record to ``experiments/dryrun/<cell>.json``.

Run one cell:   python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
Run the grid:   python -m repro.launch.dryrun --all          (subprocess per cell)
"""

import argparse
import dataclasses
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# Modelled target: one TPU v5e chip (Google Cloud "TPU v5e" published
# peaks).  The roofline terms below assume this chip whatever device the
# dry run compiles on; they are estimates, not measurements.
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, default_group: int):
    """Per-device wire bytes by collective kind (ring-algorithm estimates)."""
    out = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        g = default_group
        mg = _GROUPS_RE.search(line)
        if mg:
            g = int(mg.group(2))
        else:
            mb = _GROUPS_BRACE_RE.search(line)
            if mb:
                g = len(mb.group(1).split(","))
        g = max(g, 2)
        if kind == "all-gather":
            wire = nbytes * (g - 1) / g  # output is the gathered buffer
        elif kind == "all-reduce":
            wire = 2.0 * nbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = nbytes * (g - 1)  # output is the scattered shard
        elif kind == "all-to-all":
            wire = nbytes * (g - 1) / g
        else:  # collective-permute
            wire = nbytes
        out[kind] += wire
        counts[kind] += 1
    return out, counts


def model_flops(cfg, shape, n_params: int, n_active: int) -> float:
    tokens = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    mult = 6.0 if shape.mode == "train" else 2.0
    n = n_active if cfg.is_moe else n_params
    return mult * n * tokens


def count_params(abstract_params) -> int:
    import jax

    return int(sum(x.size for x in jax.tree.leaves(abstract_params)))


def count_active_params(cfg, abstract_params) -> int:
    """MoE: replace the expert bank by top_k/E of it."""
    import jax

    total = 0
    flat = jax.tree.flatten_with_path(abstract_params)[0]
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        frac = 1.0
        if cfg.is_moe and "moe" in keys and any(k in ("wi", "wg", "wo") for k in keys):
            frac = cfg.moe_top_k / cfg.num_experts
        total += leaf.size * frac
    return int(total)


# ---------------------------------------------------------------------------


def _lower_cell(cfg, shape, mesh, parallel, *, opt_dtype: str):
    """Lower+compile one model variant; returns (compiled, lower_s, compile_s)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import abstract_batch, abstract_inputs
    from repro.models import transformer as T
    from repro.optim import adamw as A
    from repro.parallel import sharding as SH
    from repro.train import loop as TL

    pctx = SH.make_pctx(mesh, parallel)
    pure_dp = getattr(parallel, "pure_dp", False)
    pspecs = SH.param_pspecs(cfg, mesh, fsdp=parallel.fsdp, pure_dp=pure_dp)
    pshard = SH.to_shardings(pspecs, mesh)
    aparams = T.abstract_params(cfg)
    fd = cfg.frontend_dim if cfg.frontend != "none" else 0

    def batch_shardings(ab):
        bspec = SH.batch_pspec(mesh, shape.global_batch, pure_dp=pure_dp)
        return jax.tree.map(
            lambda s: NamedSharding(mesh, P(bspec)), ab
        )

    t0 = time.time()
    if shape.mode == "train":
        opt = A.AdamWConfig(state_dtype=opt_dtype)
        astate = A.abstract_opt_state(aparams, opt)
        ospecs = A.opt_state_pspecs(pspecs, aparams, opt)
        oshard = SH.to_shardings(ospecs, mesh)
        abatch = abstract_batch(cfg.vocab_size, shape.global_batch, shape.seq_len,
                                frontend_dim=fd)
        arng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step_fn = TL.make_train_step(cfg, pctx, parallel, opt)
        jf = jax.jit(
            step_fn,
            in_shardings=(pshard, oshard, batch_shardings(abatch),
                          NamedSharding(mesh, P())),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )
        lowered = jf.lower(aparams, astate, abatch, arng)
    elif shape.mode == "prefill":
        abatch = abstract_inputs(shape.global_batch, shape.seq_len, frontend_dim=fd)

        def prefill_fn(params, batch):
            # spiking archs need an rng for Bernoulli coding; a constant key
            # is fine for lowering/measurement (it constant-folds)
            rng = jax.random.PRNGKey(0) if cfg.spiking else None
            logits, _ = T.forward(params, batch, cfg, pctx,
                                  moe_impl=parallel.moe_impl, remat="none",
                                  rng=rng)
            return logits

        jf = jax.jit(prefill_fn, in_shardings=(pshard, batch_shardings(abatch)))
        lowered = jf.lower(aparams, abatch)
    else:  # decode
        acache = T.cache_schema(cfg, shape.global_batch, shape.seq_len)
        cshard = SH.to_shardings(
            SH.cache_pspecs(cfg, mesh, shape.global_batch, shape.seq_len), mesh
        )
        atok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        tshard = NamedSharding(mesh, SH.tokens_pspec(mesh, shape.global_batch))

        def serve_step(params, cache, tokens):
            return T.decode_step(params, cache, tokens, cfg, pctx,
                                 moe_impl=parallel.moe_impl)

        jf = jax.jit(serve_step, in_shardings=(pshard, cshard, tshard),
                     out_shardings=(None, cshard), donate_argnums=(1,))
        lowered = jf.lower(aparams, acache, atok)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, t_lower, time.time() - t0


def _costs_of(compiled, chips):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    coll, counts = parse_collectives(compiled.as_text(), chips)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "counts": counts,
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, variant: str = "base") -> dict:
    import dataclasses as dc

    import jax

    from repro.configs.base import SHAPES, shape_applicable
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.tuning import model_for, parallel_for
    from repro.models import layers as L
    from repro.models import transformer as T

    cfg = model_for(get_config(arch), variant=variant)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"cell": f"{arch}:{shape_name}:{mesh_kind}", "skipped": why}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(mesh.devices.size)
    parallel = parallel_for(cfg, shape, variant=variant)

    # ---- pass A: deployable (scanned) model — compile proof + memory ----
    compiled, t_lower, t_compile = _lower_cell(cfg, shape, mesh, parallel,
                                               opt_dtype=parallel.opt_state_dtype)
    mem = compiled.memory_analysis()

    # ---- passes B/C/D: cost extrapolation (XLA counts scan bodies once) --
    # B: depth-0 (fixed costs: embed/unembed/loss/optimizer-of-embeddings)
    # C: one-period scanned        -> per-period HBM bytes (flash-like)
    # D: one-period EXACT mode     -> per-period flops + collective bytes
    #    (unrolled, einsum attention, unchunked optimizer: exact HLO counts)
    ratio = cfg.num_layers / cfg.period
    cfg0 = dc.replace(cfg, num_layers=0)
    cfg1 = dc.replace(cfg, num_layers=cfg.period)
    cB, _, _ = _lower_cell(cfg0, shape, mesh, parallel,
                           opt_dtype=parallel.opt_state_dtype)
    costB = _costs_of(cB, chips)
    cC, _, _ = _lower_cell(cfg1, shape, mesh, parallel,
                           opt_dtype=parallel.opt_state_dtype)
    costC = _costs_of(cC, chips)
    L.EXACT_FLOPS_MODE = True
    try:
        cD, _, _ = _lower_cell(cfg1, shape, mesh, parallel,
                               opt_dtype=parallel.opt_state_dtype)
        costD = _costs_of(cD, chips)
    finally:
        L.EXACT_FLOPS_MODE = False

    flops_dev = costB["flops"] + ratio * (costD["flops"] - costB["flops"])
    bytes_dev = costB["bytes"] + ratio * (costC["bytes"] - costB["bytes"])
    coll = {
        k: costB["coll"][k] + ratio * (costD["coll"][k] - costB["coll"][k])
        for k in costB["coll"]
    }
    coll = {k: max(v, 0.0) for k, v in coll.items()}
    coll_counts = costD["counts"]
    coll_bytes_dev = float(sum(coll.values()))

    aparams = T.abstract_params(cfg)
    n_params = count_params(aparams)
    n_active = count_active_params(cfg, aparams)
    mflops = model_flops(cfg, shape, n_params, n_active)

    t_comp = flops_dev / PEAK_FLOPS
    t_mem = bytes_dev / HBM_BW
    t_coll = coll_bytes_dev / ICI_BW
    terms = {"compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll}
    bottleneck = max(terms, key=terms.get)

    mem_info = {}
    if mem is not None:
        for attr in (
            "temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "generated_code_size_in_bytes",
        ):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_info[attr] = int(v)

    rec = {
        "cell": f"{arch}:{shape_name}:{mesh_kind}",
        "variant": variant,
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "chips": chips,
        "mode": shape.mode,
        "parallel": dataclasses.asdict(parallel),
        "n_params": n_params,
        "n_active_params": n_active,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll,
        "collective_counts": coll_counts,
        "collective_bytes_total_per_device": coll_bytes_dev,
        "model_flops_global": mflops,
        "hlo_flops_global": flops_dev * chips,
        "useful_flops_ratio": mflops / max(flops_dev * chips, 1.0),
        "roofline_terms_s": terms,
        "bottleneck": bottleneck,
        "roofline_step_time_s": max(terms.values()),
        "memory_analysis": mem_info,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    return rec


# ---------------------------------------------------------------------------


def _cell_path(arch, shape, mesh, variant="base") -> Path:
    tag = f"{arch}__{shape}__{mesh}" + ("" if variant == "base" else f"__{variant}")
    return OUT_DIR / f"{tag}.json"


def _force_host_devices() -> None:
    """Fake 512 host devices so production meshes build on CPU.

    Must run before the first (lazy, in-function) jax import; every jax
    touch in this module happens after main() calls this.  Respects an
    externally set XLA_FLAGS so real-accelerator runs are not clobbered.
    The ``--all`` grid re-invokes this module per cell via subprocess, so
    each child sets it for itself too.
    """
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")


def main(argv=None):
    _force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--variant", default="base", help="perf-tuning variant tag")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--meshes", default="single,multi")
    args = ap.parse_args(argv)

    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        from repro.configs.registry import ARCHS, cells

        todo = []
        for cfg, shape, ok, why in cells(include_skipped=True):
            for mesh in args.meshes.split(","):
                p = _cell_path(cfg.name, shape.name, mesh, args.variant)
                if p.exists() and not args.force:
                    continue
                if not ok:
                    p.write_text(json.dumps(
                        {"cell": f"{cfg.name}:{shape.name}:{mesh}", "skipped": why},
                        indent=1))
                    continue
                todo.append((cfg.name, shape.name, mesh))
        print(f"{len(todo)} cells to compile", flush=True)
        failures = 0
        for arch, shape, mesh in todo:
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--mesh", mesh, "--variant", args.variant]
            print(f"--- {arch}:{shape}:{mesh}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                failures += 1
                print(f"FAIL {arch}:{shape}:{mesh}\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}",
                      flush=True)
            else:
                print(r.stdout.strip().splitlines()[-1] if r.stdout.strip() else "ok",
                      flush=True)
        print(f"done; {failures} failures", flush=True)
        return 1 if failures else 0

    rec = run_cell(args.arch, args.shape, args.mesh, variant=args.variant)
    p = _cell_path(args.arch, args.shape, args.mesh, args.variant)
    p.write_text(json.dumps(rec, indent=1))
    if "skipped" in rec:
        print(f"SKIP {rec['cell']}: {rec['skipped']}")
    else:
        print(
            f"OK {rec['cell']} compile={rec['compile_s']}s "
            f"bottleneck={rec['bottleneck']} step={rec['roofline_step_time_s']:.4f}s "
            f"mem_temp={rec['memory_analysis'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
