"""Driver of spiking-ViT classification cells: the program's engine
(``repro.engine.XpikeformerEngine``), its jitted forward on the configured
backend, and argmax, driven by a closed loop of image batches: each
batch's labels reach the host before the next batch is sent.

Set-up makes the weights and a pool of image batches on the device from
the seed and runs one batch (compiling the forward, or reading it from
the cache).  The window sends batches for ``--seconds``; the rate is the
images whose labels reached the host in the window over the time to the
last of them.  Afterwards sampled batches are compared with the plain
reference.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict

import numpy as np

import harness
import trace_reduce

_now = time.perf_counter


def init_weights(c: Dict, key):
    """Float32 weights in the engine's ViT layout: linears normal with std
    1/sqrt(d_in) and zero bias, the position table normal x 0.02 (the
    repository initialiser's draws)."""
    import jax
    import jax.numpy as jnp

    d, f = c["dim"], c["mlp_ratio"] * c["dim"]
    pdim = c["patch_size"] ** 2 * c["in_channels"]
    n = (c["image_size"] // c["patch_size"]) ** 2

    def lin(k, din, dout):
        return {"w": jax.random.normal(k, (din, dout), jnp.float32) / np.sqrt(din),
                "b": jnp.zeros((dout,), jnp.float32)}

    def block(k):
        ks = jax.random.split(k, 6)
        ln = {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
        return {"ln1": ln, "ln2": ln, "wq": lin(ks[0], d, d), "wk": lin(ks[1], d, d),
                "wv": lin(ks[2], d, d), "wo": lin(ks[3], d, d),
                "w1": lin(ks[4], d, f), "w2": lin(ks[5], f, d)}

    ks = jax.random.split(key, c["depth"] + 3)
    return {"patch": lin(ks[0], pdim, d),
            "pos": jax.random.normal(ks[1], (n, d), jnp.float32) * 0.02,
            "blocks": [block(ks[2 + i]) for i in range(c["depth"])],
            "head": lin(ks[-1], d, c["num_classes"])}


def make_weights(c: Dict, seed: int):
    """The weights of ``init_weights`` from the seed, in one jitted call."""
    import jax

    return jax.jit(lambda k: init_weights(c, k))(harness.seed_key(seed, 11))


_SIZES = ("depth", "dim", "num_heads", "T", "mlp_ratio", "image_size",
          "patch_size", "num_classes", "in_channels")


def build_engine(c: Dict):
    from repro.configs.xpikeformer import SPIKING_ARCHS
    from repro.engine import XpikeformerEngine

    task, base = SPIKING_ARCHS[c["registry_name"]]
    cfg = dataclasses.replace(base, mode="ssa", **{k: c[k] for k in _SIZES})
    return XpikeformerEngine.from_config(cfg, task=task, backend=c["backend"])


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        devs, hooks: Dict) -> Dict:
    import jax
    import jax.numpy as jnp

    c = dict(cell.config)
    counter = harness.CompileCounter()
    params = make_weights(c, seed)
    eng = build_engine(c)
    fwd = eng.jit_forward()
    if "forward" in hooks:
        fwd = hooks["forward"](fwd)
    traffic = cell.traffic().make(cell.mix, harness.seed_key(seed, 12),
                                  c["image_size"], c["in_channels"],
                                  c["num_classes"])
    pick = jax.jit(lambda lg: jnp.argmax(lg, axis=-1))

    def send(i):
        """One batch: its logits stay on the device, its labels reach the
        host."""
        with harness.span("bench.vit_dispatch"):
            lg = fwd(params, traffic.batch_images(i), traffic.batch_key(i))
            lab = pick(lg)
        with harness.span("bench.vit_labels"):
            np.asarray(lab)
        return lg

    send(1 << 20)  # warm-up: compiles (or reads from the cache) forward and argmax
    tw = harness.TraceWindow(cell.name) if trace else None
    lead = min(1.0, seconds / 4)
    trace_at = (lead, min(seconds, lead + harness.TRACE_SECONDS))
    n0 = counter.n
    setup_s = _now() - t_start
    t0 = _now()
    end = t0 + seconds
    done = []  # (batch index, time its labels reached the host)
    logits_of = {}  # batch index -> its logits, left on the device
    i = 0
    while _now() < end:
        if tw is not None and tw.t0 is None and _now() >= t0 + trace_at[0]:
            tw.start()
        if tw is not None and tw.t0 is not None and tw.t1 is None \
                and _now() >= t0 + trace_at[1]:
            tw.stop()
        logits_of[i] = send(i)
        done.append((i, _now()))
        i += 1
    if tw is not None and tw.t1 is None:
        tw.stop()
    compiles_in_window = counter.n - n0
    device = harness.device_info(devs)
    in_window = [i for i, t in done if t <= end]
    images = len(in_window) * traffic.batch
    # every image whose label reached the host in the window, over the time
    # from the window's start to the last of those labels
    t_last = done[len(in_window) - 1][1] if in_window else end
    e2e = {"images_s": images / (t_last - t0)}
    harness.info(setup_s=setup_s, compiles_in_window=compiles_in_window,
                 batches_sent=len(done), batches_in_window=len(in_window),
                 images_in_window=images, last_label_s=t_last - t0, **e2e)
    ctx = {"config": c, "peaks": None,
           "e2e": e2e, "done": done, "t0": t0, "end": end,
           "batch": traffic.batch, "trace": None, "trace_window": None}
    breakdown = None
    if trace:
        ctx["peaks"] = harness.peaks_for(device["kind"])
        red = trace_reduce.reduce(tw.xplane(), tw.window_s)
        ctx["trace"] = red
        ctx["trace_window"] = (tw.t0, tw.t1)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
    rng = np.random.default_rng([seed, 5])
    chosen = sorted(rng.choice(len(in_window), size=min(
        c["check"]["sample_batches"], len(in_window)), replace=False))
    batches = [(traffic.batch_images(in_window[k]),
                traffic.batch_key(in_window[k]), logits_of[in_window[k]])
               for k in chosen]
    del logits_of
    del eng, fwd
    gc.collect()
    ref = cell.reference()
    cmp = (ref.compare(params, c, batches) if batches
           else {"max_logit_err": float("inf"), "images_compared": 0})
    if "control" in hooks:
        hooks["control"](ref, params, c, batches, cmp)
    lim = c["check"]
    checks = {"max_logit_err": {"value": cmp["max_logit_err"],
                                "limit": lim["max_logit_err"]},
              "images_compared": {"value": cmp["images_compared"],
                                  "limit": lim["min_images_compared"]}}
    correct = (cmp["max_logit_err"] <= lim["max_logit_err"]
               and cmp["images_compared"] >= lim["min_images_compared"])
    if trace:
        metrics = harness.read_per_layer(cell.per_layer(), ctx)
    else:
        metrics = {}
        for m in cell.end_to_end():
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return {"correct": correct, "attempted": len(done), "failed": 0,
            "metrics": metrics, "device": device, "checks": checks,
            "breakdown": breakdown}
