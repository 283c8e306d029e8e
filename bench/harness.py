"""What every cell shares: finding its files by name, the chip check,
compile counting, the traced window, per-layer readers and the result line.

Nothing here names a configuration, a mix or a metric.  A cell of
``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``,
whose ``driver`` and ``reference`` keys name ``bench/drivers/<driver>.py``
and ``bench/reference/<reference>.py``) and a mix
(``bench/traffic/<mix>.json``, whose ``kind`` names
``bench/traffic/<kind>.py``); a per-layer metric ``m`` is read by
``bench/metrics/<m>.py``.  A new configuration, mix or metric is new files.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"  # traces and other run-time files (gitignored)
TRACE_SECONDS = 4.0  # length of a --trace 1 run's profiler window


class CellError(RuntimeError):
    """The cell cannot run here (no chip, missing files): no result."""


# -- files found by name -------------------------------------------------


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None):
    """Import one file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise CellError(f"missing benchmark file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        name or f"bench_plugin_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration and mix."""

    def __init__(self, name: str, bench_json: Optional[Path] = None):
        spec = read_json(bench_json or ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise CellError(f"no workload {name!r} in BENCHMARK.json")
        self.spec = spec
        self.workload = cells[name]
        self.name = name
        confs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = confs[self.workload["config"]]
        self.config = read_json(ROOT / self.config_entry["file"])
        self.mix = read_json(BENCH / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])

    def end_to_end(self) -> List[Dict]:
        """The end-to-end metrics this cell reports (``setup_s`` included)."""
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict]:
        """The per-layer metrics read in this cell's traced run."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def driver(self):
        return load_module(BENCH / "drivers" / f"{self.config['driver']}.py")

    def reference(self):
        return load_module(BENCH / "reference" / f"{self.config['reference']}.py")

    def traffic(self):
        return load_module(BENCH / "traffic" / f"{self.mix['kind']}.py")


def read_per_layer(metrics: List[Dict], ctx: Dict) -> Dict[str, Dict]:
    """Run each metric's reader; a reader that finds nothing returns None
    and the metric is left out of the line."""
    out = {}
    for m in metrics:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            if not math.isfinite(v):
                raise ValueError(f"per-layer metric {m['name']} read {v}")
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def seed_key(seed: int, *salt: int):
    """A JAX PRNG key (raw uint32 pair) from a seed of any size and salts."""
    import jax.numpy as jnp
    import numpy as np

    words = np.random.default_rng([int(seed), *salt]).integers(
        0, 2 ** 32, size=2, dtype=np.uint64)
    return jnp.asarray(words.astype(np.uint32))


# -- device and compile cache ----------------------------------------------


def prepare_jax() -> None:
    """Compile cache inside the checkout (or where the environment says),
    every program cached; compiler logs off.  Before JAX starts."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_devices(chips: int):
    """The chips this cell asks for, or CellError: never the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX's platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise CellError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_info(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> Dict[str, float]:
    table = read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise CellError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts backend compilations (JAX's monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


class TraceWindow:
    """A ``jax.profiler`` session without the Python tracer; host spans
    come from ``jax.profiler.TraceAnnotation`` in the harness's files."""

    def __init__(self, name: str):
        self.dir = OUT / "trace" / name
        self.t0 = self.t1 = None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window_open"):
            self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation("bench.window_close"):
            self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def xplane(self) -> Path:
        found = sorted(self.dir.glob("**/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return found[-1]


def span(name: str):
    """A host span in the profiler's trace (a no-op when none is open)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- output ------------------------------------------------------------------


def info(**fields) -> None:
    """An earlier line of standard output (the driver reads only the last)."""
    print(json.dumps({"info": fields}, default=float), flush=True)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def emit(*, correct: bool, attempted: int, failed: int,
         metrics: Dict[str, Dict], device: Dict, checks: Dict[str, Dict],
         breakdown: Optional[Dict] = None) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output, ``checks`` as its last key."""
    for k, v in checks.items():
        print(f"[check] {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    res: Dict[str, Any] = {"correct": bool(correct), "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = checks
    print(json.dumps(res), flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, hooks: Optional[Dict[str, Callable]] = None,
             need_chip: bool = True) -> Dict:
    """Run one cell and return what ``emit`` prints.  ``hooks`` lets a test
    break the path under the harness; ``need_chip=False`` lets a test run
    on the CPU (a benchmark run never passes it)."""
    import jax

    hooks = hooks or {}
    cell = Cell(name)
    if "mix" in hooks:
        cell.mix = hooks["mix"](cell.mix)
    devs = check_devices(cell.chips) if need_chip else jax.devices()[:cell.chips]
    # float products run at the precision the configuration states
    # ("default" is XLA's own choice for the platform)
    prec = cell.config["matmul_precision"]
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)
    driver = cell.driver()
    return driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=t_start, devs=devs, hooks=hooks)
