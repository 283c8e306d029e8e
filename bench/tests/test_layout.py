"""New cells, mixes, configurations and per-layer metrics are new files:
the harness finds them by the names in BENCHMARK.json."""

import json
import subprocess
import sys

import harness


def test_scratch_cell_from_new_files_only(tmp_path, monkeypatch):
    import tiny

    root = tiny.make_tree(tmp_path)
    # the paper's other decoder, gpt-4-256, under the decode mix: data only
    cfg = json.loads((root / "bench/configs/xpikeformer-gpt-8-512.json").read_text())
    cfg.update(name="xpikeformer-gpt-4-256", registry_name="xpikeformer-gpt-4-256",
               num_layers=4, d_model=256, num_heads=4, num_kv_heads=4, d_ff=1024)
    (root / "bench/configs/xpikeformer-gpt-4-256.json").write_text(json.dumps(cfg))
    (root / "bench/metrics/new_share.decode.py").write_text(
        "def read(ctx):\n    return None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "xpikeformer-gpt-4-256", "source": "s",
                            "file": "bench/configs/xpikeformer-gpt-4-256.json",
                            "reduced": [], "why": "w"})
    spec["workloads"].append({"name": "gpt-4-256.decode", "chips": 1, "why": "w",
                              "config": "xpikeformer-gpt-4-256", "traffic": "decode"})
    spec["per_layer"].append({"name": "new_share.decode", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "decode step", "moves": "tok_s",
                              "workloads": ["gpt-4-256.decode"]})
    m = next(m for m in spec["end_to_end"] if m["name"] == "tok_s")
    m["workloads"].append("gpt-4-256.decode")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")

    cell = harness.Cell("gpt-4-256.decode")
    assert cell.config["d_model"] == 256 and cell.mix["kind"] == "requests"
    assert {m["name"] for m in cell.end_to_end()} == {"tok_s", "setup_s"}
    assert "new_share.decode" in {m["name"] for m in cell.per_layer()}
    assert hasattr(cell.driver(), "run") and hasattr(cell.reference(), "compare")
    assert hasattr(cell.traffic(), "make")
    assert harness.read_per_layer(
        [m for m in cell.per_layer() if m["name"] == "new_share.decode"], {}) == {}
    lm = cell.driver()
    assert lm.program_config(cell.config).d_model == 256


def test_every_declared_name_has_its_files():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.Cell(w["name"])
        cell.driver(), cell.reference(), cell.traffic()
        assert cell.end_to_end() and cell.per_layer()
    for m in spec["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_no_chip_no_result():
    """Without a TPU the benchmark exits non-zero and prints no result."""
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "gpt-8-512.decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(harness.ROOT / ".bench_out")})
    assert p.returncode != 0 and p.stdout.strip() == ""
