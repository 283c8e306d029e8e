"""End-to-end system behaviour: launcher training, serving, dry-run plumbing."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import serve as LS
from repro.launch import train as LT


@pytest.mark.slow
def test_launcher_trains_and_checkpoints(tmp_path):
    losses, probe0, probe1 = LT.run(
        "granite-3-8b", steps=25, ckpt_dir=str(tmp_path), ckpt_every=10,
        log_every=0, seed=2, probe=True,
    )
    assert len(losses) == 25
    # fixed-batch probe: per-step losses are fresh batches, and the
    # tied-embedding smoke starts calibrated at the stream's entropy floor,
    # so first-vs-last fresh-batch loss is inter-batch noise (~+-0.05) while
    # the trained model's gain on a held-fixed batch is ~0.3 — deterministic.
    assert probe1 < probe0 - 0.05
    steps = {p.name for p in tmp_path.glob("step_*")}
    assert any(s.endswith("00000025") for s in steps)


@pytest.mark.slow
def test_launcher_moe_arch(tmp_path):
    losses = LT.run("phi3.5-moe-42b-a6.6b", steps=12, ckpt_dir=str(tmp_path),
                    ckpt_every=0, log_every=0)
    assert losses[-1] < losses[0] * 1.2  # moves; MoE smoke is noisy


def test_serve_continuous_batching():
    outs, _ = LS.serve("yi-9b", n_requests=5, slots=2, max_new=4, cache_len=32)
    assert len(outs) == 5
    assert all(len(o) == 4 for o in outs)


def test_dryrun_collective_parser():
    from repro.launch.dryrun import parse_collectives

    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[8,8]{1,0} %x), replica_groups=[16,16]<=[256]
  %ar = f32[64]{0} all-reduce(f32[64]{0} %y), replica_groups={{0,1,2,3}}
  %a2a = bf16[16,640,7168]{2,1,0} all-to-all(bf16[16,640,7168]{2,1,0} %z), replica_groups=[16,16]<=[256]
"""
    bytes_by, counts = parse_collectives(hlo, 256)
    assert counts["all-gather"] == 1 and counts["all-reduce"] == 1
    assert counts["all-to-all"] == 1
    assert bytes_by["all-gather"] == pytest.approx(8 * 128 * 2 * 15 / 16)
    assert bytes_by["all-reduce"] == pytest.approx(2 * 64 * 4 * 3 / 4)


def test_dryrun_grid_results_exist():
    """The multi-pod dry-run grid must be green: every (arch x shape x mesh)
    cell either compiled or is a documented skip."""
    d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    if not d.exists():
        pytest.skip("dry-run grid not generated yet")
    recs = [json.loads(p.read_text()) for p in d.glob("*.json")]
    base = [r for r in recs if r.get("variant", "base") == "base" or "skipped" in r]
    compiled = [r for r in base if "skipped" not in r]
    assert len(compiled) >= 60, f"only {len(compiled)} compiled cells"
    multi = [r for r in compiled if r.get("mesh") == "multi"]
    assert len(multi) >= 30  # the pod axis shards for every runnable cell
    for r in compiled:
        assert r["flops_per_device"] > 0
        assert r["roofline_step_time_s"] > 0
