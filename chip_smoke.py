#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: the quickest proof the system starts.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the (data, model) mesh path only

One chip: the paper's largest spiking decoder, ``xpikeformer-gpt-8-512``
(8 layers, d=512, 8 heads x 64, d_ff 2048, T=4) at full width with random
seeded weights, served through :func:`repro.launch.serve.serve` on the
compiled Pallas kernels — dense cache, paged cache, and programmed onto
simulated PCM with drift and GDC recalibration — and the spiking ViT
``xpikeformer-vit-8-768`` classifying a seeded synthetic batch through
:class:`repro.engine.XpikeformerEngine`.  Every run is compared on the same
chip with the ``integer`` backend, the repo's bit-exact oracle: tokens,
labels and logits must be identical.  The Pallas runs must be compiled,
not interpreted: the backend's interpret flag is off and the decode step's
program holds Mosaic kernels (``tpu_custom_call``).

``--four-chips`` serves the same decoder over ``(data, model)`` meshes
(1x4: four-way tensor parallel over the 8 heads; 2x2) and compares their
tokens with the single-device integer oracle.

Each phase prints one JSON line; tok/s figures in them are informational
(one short run, compiles included), not measurements.  The last line is
``{"ok": true, "device": {...}}`` when every phase passed; the script exits
non-zero otherwise, and without any result when JAX finds no TPU.  All
work runs in this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GPT = "xpikeformer-gpt-8-512"
VIT = "xpikeformer-vit-8-768"
SERVE = dict(n_requests=8, slots=4, max_new=16, cache_len=64, seed=0)
# seconds the XLA/Mosaic backend spent compiling, summed over the process
_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def _serve(backend: str, **kw):
    from repro.launch.serve import serve

    t0 = time.perf_counter()
    outs, sch = serve(GPT, smoke=False, backend=backend, **{**SERVE, **kw})
    return outs, sch, time.perf_counter() - t0


def _compiled_facts(sch) -> dict:
    """The scheduler's Pallas backend is compiled, and so is its step."""
    backend = getattr(sch.backend, "inner", sch.backend)  # mesh: sharded
    hlo = sch.lower_decode_step().compile().as_text()
    return {"interpret": backend.interpret,
            "mosaic_kernels": "tpu_custom_call" in hlo,
            "plan": sch.plan.describe() if sch.plan is not None else None}


def _serve_phase(oracle=None, **kw):
    """Serve with pallas and integer; tokens must match bit for bit."""
    outs_p, sch, dt_p = _serve("pallas", **kw)
    outs_i = oracle if oracle is not None else _serve("integer", **kw)[0]
    facts = _compiled_facts(sch)
    st = sch.stats
    res = {"tokens_match": outs_p == outs_i,
           "tokens": sum(len(o) for o in outs_p),
           "pallas_wall_s": dt_p,
           "pallas_tok_s_informational": st.tokens_per_sec,
           **facts}
    res["ok"] = (res["tokens_match"] and res["tokens"] > 0
                 and facts["interpret"] is False and facts["mosaic_kernels"])
    return res, sch


def phase_dense() -> dict:
    res, _ = _serve_phase()
    return res


def phase_paged() -> dict:
    res, _ = _serve_phase(paged=True, page_len=8)
    return res


def phase_programmed() -> dict:
    """PCM-programmed weights, 60 device-seconds per step, GDC every 600 s;
    then the Pallas drift fold on one served crossbar vs the device's own."""
    import jax
    import numpy as np

    from repro import aimc_device as AD
    from repro.kernels import ops as KOPS

    res, sch = _serve_phase(program=True, drift_step_s=60.0,
                            recal_every_s=600.0)
    res["recalibrations"] = sch.stats.recalibrations
    res["device_s"] = sch.stats.t_device_s

    def is_state(x):
        return isinstance(x, AD.AIMCDeviceState)

    st = next(x for x in jax.tree.leaves(sch.params, is_leaf=is_state)
              if is_state(x))
    d_in, d_out = st.levels.shape[-2:]

    def first(x, shape):
        return x.reshape((-1,) + shape)[0]

    img_gain = int(round(1.0 / float(first(st.img_inv, ()))))
    got = KOPS.drift_requantize(
        first(st.levels, (d_in, d_out)), first(st.eps, (d_in, d_out)),
        first(st.nu, (d_in, d_out)), first(st.t_seconds, ()),
        t0=AD.AIMCConfig().drift_t0_s, img_gain=img_gain)
    want = first(st.levels_t, (d_in, d_out))
    res["drift_fold_match"] = bool(np.array_equal(np.asarray(got),
                                                  np.asarray(want)))
    res["ok"] = (res["ok"] and res["recalibrations"] >= 1
                 and res["drift_fold_match"])
    return res


def phase_vit() -> dict:
    """ViT-8-768 on a seeded synthetic batch: pallas == integer."""
    import jax
    import numpy as np

    from repro.data.synthetic_images import ImageConfig, sample_batch
    from repro.engine import XpikeformerEngine

    rng = jax.random.PRNGKey(1)
    out = {}
    for name in ("pallas", "integer"):
        eng = XpikeformerEngine.from_config(VIT, backend=name)
        eng.init(jax.random.PRNGKey(0))
        images = sample_batch(jax.random.PRNGKey(0),
                              ImageConfig(size=eng.cfg.image_size), 8)["images"]
        t0 = time.perf_counter()
        labels = np.asarray(eng.classify(images, rng))
        out[name] = (labels, np.asarray(eng.forward(images, rng)),
                     time.perf_counter() - t0, eng.backend.interpret
                     if name == "pallas" else None)
    (lp, xp, dt, interp), (li, xi, _, _) = out["pallas"], out["integer"]
    res = {"labels_match": bool(np.array_equal(lp, li)),
           "logits_match": bool(np.array_equal(xp, xi)),
           "labels": lp.tolist(), "interpret": interp,
           "pallas_classify_s": dt}
    res["ok"] = res["labels_match"] and res["logits_match"] and not interp
    return res


def phase_mesh(shape: str, oracle) -> dict:
    res, _ = _serve_phase(oracle=oracle, mesh_spec=shape)
    res["mesh"] = shape
    return res


def _run(name: str, fn, results: dict) -> None:
    t0, c0 = time.perf_counter(), _COMPILE_S[0]
    try:
        res = fn()
    except Exception as e:  # a failed phase fails the run; the rest still run
        traceback.print_exc()
        res = {"ok": False, "error": repr(e)[:2000]}
    res["phase"] = name
    res["phase_s"] = time.perf_counter() - t0
    res["backend_compile_s"] = _COMPILE_S[0] - c0
    results[name] = res
    print(json.dumps(res, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data, model) mesh path on 4 chips")
    a = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    want = 4 if a.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"device_kind": devs[0].device_kind,
                      "devices": len(devs)}), flush=True)

    results: dict = {}
    if a.four_chips:
        oracle, _, _ = _serve("integer")  # single-device integer oracle
        for shape in ("1x4", "2x2"):
            _run(f"gpt-mesh-{shape}", lambda s=shape: phase_mesh(s, oracle),
                 results)
    else:
        _run("gpt-dense", phase_dense, results)
        _run("gpt-paged", phase_paged, results)
        _run("gpt-programmed", phase_programmed, results)
        _run("vit", phase_vit, results)

    failed = [n for n, r in results.items() if not r["ok"]]
    if failed:
        print(json.dumps({"ok": False, "failed": failed}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
