"""Backend-parity tests for the unified XpikeformerEngine API.

The contract of ``repro.engine``:

* ``pallas`` (interpreted on CPU) is **bit-exact** against the ``integer``
  hardware oracle given the same PRNG key — through the *full* spiking
  ViT and GPT forwards, not just per-kernel.
* ``reference`` (float + straight-through) agrees with ``integer`` in
  distribution: at T=32 the time-averaged outputs (read out linearly by
  the classifier head, so logit differences == rate differences) match
  within a statistical tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.xpikeformer import SPIKING_ARCHS
from repro.data.icl_mimo import MIMOConfig, sample_batch as mimo_batch
from repro.engine import (BACKENDS, IntegerBackend, PallasBackend,
                          ReferenceBackend, XpikeformerEngine, get_backend)

ARCH_INPUTS = {
    "xpikeformer-vit-smoke": lambda key: jax.random.uniform(key, (4, 16, 16, 3)),
    "xpikeformer-gpt-smoke": lambda key: mimo_batch(key, MIMOConfig(), 4)["features"],
}


def _engine(name, backend, T=None, params=None):
    task, cfg = SPIKING_ARCHS[name]
    if T is not None:
        cfg = dataclasses.replace(cfg, T=T)
    eng = XpikeformerEngine.from_config(cfg, task=task, backend=backend)
    eng.params = params
    return eng


@pytest.mark.parametrize("arch", sorted(ARCH_INPUTS))
def test_pallas_bit_exact_vs_integer_oracle(arch, rng):
    """Full-model forward: pallas kernels == integer hardware oracle, bit for bit."""
    x = ARCH_INPUTS[arch](jax.random.fold_in(rng, 1))
    ei = _engine(arch, "integer")
    params = ei.init(rng)
    ep = _engine(arch, "pallas", params=params)
    li = ei.forward(x, jax.random.fold_in(rng, 2))
    lp = ep.forward(x, jax.random.fold_in(rng, 2))
    np.testing.assert_array_equal(np.asarray(li), np.asarray(lp))


@pytest.mark.parametrize("arch", sorted(ARCH_INPUTS))
def test_reference_matches_integer_rates(arch, rng):
    """reference vs integer output rates at T=32 (statistical tolerance).

    The head reads the pooled firing rates linearly, so logit agreement is
    rate agreement.  5-bit weight quantisation on the integer side plus
    finite-T sampling noise bound the gap well below the logit scale."""
    x = ARCH_INPUTS[arch](jax.random.fold_in(rng, 1))
    er = _engine(arch, "reference", T=32)
    params = er.init(rng)
    ei = _engine(arch, "integer", T=32, params=params)
    lr_ = er.forward(x, jax.random.fold_in(rng, 2))
    li = ei.forward(x, jax.random.fold_in(rng, 2))
    scale = float(jnp.mean(jnp.abs(lr_)))
    assert float(jnp.mean(jnp.abs(lr_ - li))) < max(0.1 * scale, 0.05)


@pytest.mark.parametrize("arch", sorted(ARCH_INPUTS))
def test_all_backends_one_call_site(arch, rng):
    """The acceptance contract: one engine call site, every backend."""
    x = ARCH_INPUTS[arch](jax.random.fold_in(rng, 1))
    params = _engine(arch, "reference").init(rng)
    for backend in sorted(BACKENDS):
        eng = _engine(arch, backend, params=params)
        logits = eng.forward(x, jax.random.fold_in(rng, 2))
        assert jnp.isfinite(logits).all(), f"{backend}: non-finite logits"


def test_programmed_inference_stays_bit_exact(rng):
    """program() -> PCM state; integer and pallas still agree bit-for-bit."""
    arch = "xpikeformer-vit-smoke"
    x = ARCH_INPUTS[arch](jax.random.fold_in(rng, 1))
    ei = _engine(arch, "integer")
    ei.init(rng)
    hw = ei.program(jax.random.fold_in(rng, 3))
    assert ei.sim.wmode == "hw"
    ep = _engine(arch, "pallas", params=hw)
    ep.sim = ei.sim
    li = ei.forward(x, jax.random.fold_in(rng, 2))
    lp = ep.forward(x, jax.random.fold_in(rng, 2))
    np.testing.assert_array_equal(np.asarray(li), np.asarray(lp))


def test_programmed_lifecycle_on_matrix_backend(rng, engine_backend):
    """program -> drift -> recalibrate executes on the CI-matrix backend
    (XPIKE_BACKEND): every substrate runs the programmed device state, and
    lifecycle updates change leaf values only (jit caches stay warm)."""
    arch = "xpikeformer-gpt-smoke"
    x = ARCH_INPUTS[arch](jax.random.fold_in(rng, 1))
    eng = _engine(arch, engine_backend)
    eng.init(rng)
    eng.program(jax.random.fold_in(rng, 3))
    treedef = jax.tree.structure(eng.params)
    shapes = [(l.shape, l.dtype) for l in jax.tree.leaves(eng.params)]
    for t in (0.0, 3600.0, 3.15e7):
        eng.drift_to(t)
        eng.recalibrate()
        logits = eng.forward(x, jax.random.fold_in(rng, 2))
        assert jnp.isfinite(logits).all(), f"{engine_backend} t={t}"
        assert jax.tree.structure(eng.params) == treedef
        assert [(l.shape, l.dtype) for l in jax.tree.leaves(eng.params)] == shapes


def test_task_helpers(rng):
    vit = _engine("xpikeformer-vit-smoke", "pallas")
    vit.init(rng)
    images = ARCH_INPUTS["xpikeformer-vit-smoke"](jax.random.fold_in(rng, 1))
    labels = vit.classify(images, jax.random.fold_in(rng, 2))
    assert labels.shape == (4,) and labels.dtype in (jnp.int32, jnp.int64)

    gpt = _engine("xpikeformer-gpt-smoke", "integer")
    gpt.init(rng)
    feats = ARCH_INPUTS["xpikeformer-gpt-smoke"](jax.random.fold_in(rng, 1))
    syms = gpt.detect_symbols(feats, jax.random.fold_in(rng, 2))
    assert syms.shape == feats.shape[:2]


def test_reference_backend_is_differentiable(rng):
    eng = _engine("xpikeformer-vit-smoke", "reference")
    params = eng.init(rng)
    images = ARCH_INPUTS["xpikeformer-vit-smoke"](jax.random.fold_in(rng, 1))

    def loss(p):
        return jnp.sum(eng.forward(images, jax.random.fold_in(rng, 2), p) ** 2)

    g = jax.grad(loss)(params)
    gn = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_backend_registry():
    assert isinstance(get_backend("reference"), ReferenceBackend)
    assert isinstance(get_backend("integer"), IntegerBackend)
    assert isinstance(get_backend(None), ReferenceBackend)
    pb = get_backend("pallas")
    assert isinstance(pb, PallasBackend) and pb.interpret  # CPU: interpreted
    inst = IntegerBackend()
    assert get_backend(inst) is inst
    with pytest.raises(KeyError):
        get_backend("tpu-v7")
    with pytest.raises(KeyError):
        XpikeformerEngine.from_config("not-an-arch")


def test_generic_lm_stack_backend_dispatch(rng):
    """models/transformer.py spiking path runs on a non-default backend."""
    from repro.configs.registry import reduced_config
    from repro.models import transformer as T

    cfg = reduced_config("xpikeformer-gpt-4-256")
    params = T.init_params(rng, cfg)
    batch = {"tokens": jax.random.randint(rng, (2, 17), 0, cfg.vocab_size, jnp.int32)}
    for backend in ("integer", "pallas"):
        loss, _ = T.loss_fn(params, batch, cfg, moe_impl="dense", remat="none",
                            rng=rng, backend=get_backend(backend))
        assert jnp.isfinite(loss)
