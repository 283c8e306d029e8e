"""A scratch copy of the benchmark with tiny cells, for CPU tests: the
benchmark's files are copied, new cells are added as new files only."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_GPT = {
    "name": "tiny-gpt", "source": "test", "registry_name": "xpikeformer-gpt-8-512",
    "driver": "lm_serve", "reference": "lm_spiking",
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 16, "d_ff": 128, "vocab_size": 64, "spike_T": 4,
    "weight_bits": 5, "lif_beta": 0.5, "lif_threshold": 1.0,
    "layernorm_eps": 1e-06, "dtype": "float32", "cache_len": 64,
    "serving": {"backend": "pallas", "paged": True, "page_len": 8, "slots": 4,
                "decode_kernel": "auto"},
    "matmul_precision": "highest",
    "check": {"sample_requests": 4, "min_tokens_compared": 8,
              "max_logit_gap": 1e-3},
    "reduced": []}
TINY_VIT = {
    "name": "tiny-vit", "source": "test", "registry_name": "xpikeformer-vit-smoke",
    "driver": "vit_classify", "reference": "vit_spiking",
    "depth": 2, "dim": 32, "num_heads": 2, "T": 3, "mlp_ratio": 4,
    "image_size": 16, "patch_size": 4, "in_channels": 3, "num_classes": 10,
    "weight_bits": 5, "lif_beta": 0.5, "lif_threshold": 1.0,
    "dtype": "float32", "backend": "pallas",
    "matmul_precision": "highest",
    "check": {"sample_batches": 2, "min_images_compared": 8,
              "max_logit_err": 1e-4},
    "reduced": []}
MIXES = {
    "tiny_decode": {"kind": "requests", "arrivals": {"loop": "closed", "clients": 6},
                    "prompt_len": {"dist": "uniform", "lo": 4, "hi": 12},
                    "output_len": {"dist": "uniform", "lo": 4, "hi": 12},
                    "block": 8},
    "tiny_serve": {"kind": "requests",
                   "arrivals": {"loop": "open", "rate_rps": 4.0, "gap_cv": 2.0},
                   "shared": {"frac": 0.5, "prefixes": 2, "prefix_len": 16,
                              "tail_len": {"dist": "uniform", "lo": 2, "hi": 6}},
                   "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                                  "lo": 3, "hi": 20},
                   "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.8,
                                  "lo": 2, "hi": 12},
                   "block": 8},
    "tiny_classify": {"kind": "image_batches", "batch": 8, "pool": 2},
}
CELLS = {"tiny.decode": ("tiny-gpt", "tiny_decode"),
         "tiny.serve": ("tiny-gpt", "tiny_serve"),
         "tiny.classify": ("tiny-vit", "tiny_classify")}


def make_tree(dst: Path) -> Path:
    """``dst`` holding a copy of the benchmark plus the tiny cells, each
    added as new files and new entries of BENCHMARK.json."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(ROOT / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cfg in (TINY_GPT, TINY_VIT):
        f = f"bench/configs/{cfg['name']}.json"
        (dst / f).write_text(json.dumps(cfg))
        spec["configs"].append({"name": cfg["name"], "source": "test",
                                "file": f, "reduced": [], "why": "test"})
    for name, mix in MIXES.items():
        (dst / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    for cell, (cfg, mix) in CELLS.items():
        spec["workloads"].append({"name": cell, "config": cfg, "traffic": mix,
                                  "chips": 1, "why": "test"})
    real = {"gpt-8-512.decode": "tiny.decode", "gpt-8-512.serve": "tiny.serve",
            "vit-8-768.classify": "tiny.classify"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [real[w] for w in m["workloads"]]
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst
