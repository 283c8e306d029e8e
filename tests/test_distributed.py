"""Mesh-sharded distributed execution: sharded == single-device, bit-exact.

The subsystem contracts (see ``repro/distributed``):

* **forward bit-exactness** — the full spiking forward placed on a
  (data, model) mesh (batch data-parallel, spiking linears / SSA attention
  tensor-parallel) produces bit-identical logits to the single-device
  backend: every sharded reduction is over integer-valued operands and
  every PRN draw is at logical shapes.
* **scheduler bit-exactness** — a whole ``BatchScheduler.run()`` with
  mid-flight admission and evictions on a >=4-device mesh decodes exactly
  the single-device integer oracle's tokens, on both digital substrates
  (integer and pallas).
* **programmed-AIMC lifecycle** — the drift + GDC path (device clock
  advance, image refolds, integer-sum calibration reads) is sharding-
  invariant, and none of it recompiles the jitted decode step.
* **placement** — device-state leaves get per-field specs on the crossbar
  matrix view; the spiking KV cache shards its head axis over ``model``.

These tests run on the 8-device host platform forced by conftest
(``--xla_force_host_platform_device_count=8``); they skip gracefully if a
caller overrides XLA_FLAGS with fewer devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import aimc_device as AD
from repro.configs.registry import reduced_config
from repro.engine import IntegerBackend, PallasBackend
from repro.launch.mesh import make_serving_mesh, parse_mesh_spec
from repro.models import transformer as T
from repro.parallel import sharding as SH
from repro.serving import BatchScheduler

SPIKING = "xpikeformer-gpt-4-256"
ANN = "yi-9b"

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device host platform")


@pytest.fixture(scope="module")
def spiking_setup():
    cfg = reduced_config(SPIKING)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device host platform")
    return make_serving_mesh((2, 4))


def _prompt(i, length):
    return list(range(3 + i, 3 + i + length))


def _oracle_run(cfg, params, prompts, max_new, *, slots=2, cache_len=32,
                seed0=100, drift=None, evict_after=None):
    sch = BatchScheduler(params, cfg, IntegerBackend(), slots=slots,
                         cache_len=cache_len, drift=drift)
    rids = [sch.submit(p, max_new, seed=seed0 + i) for i, p in enumerate(prompts)]
    if evict_after is not None:
        for _ in range(evict_after):
            sch.step()
        sch.evict(0, requeue=True)
    outs = sch.run()
    return [outs[r] for r in rids], sch


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("backend_cls", [IntegerBackend, PallasBackend])
def test_mesh_forward_bit_exact(spiking_setup, mesh, backend_cls):
    """Full spiking forward on the (2, 4) mesh == single device, bitwise."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 12), 0,
                                cfg.vocab_size, jnp.int32)
    rng = jax.random.PRNGKey(3)
    ref = T.forward(params, {"tokens": tokens}, cfg, rng=rng,
                    backend=backend_cls(), remat="none")[0]
    ex = Executor(params, cfg, backend_cls(), mesh)
    got = ex.forward(tokens, rng)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ---------------------------------------------------------------------------
# Scheduler decode (the acceptance criterion)
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("backend_cls", [IntegerBackend, PallasBackend])
def test_sharded_scheduler_bit_exact_vs_integer_oracle(
        spiking_setup, mesh, backend_cls):
    """Sharded integer/pallas decode through a full BatchScheduler.run()
    with mid-flight admissions and evictions on a (2, 4) mesh decodes the
    single-device integer oracle's tokens bit-for-bit — and the jitted
    sharded decode_step compiles exactly once."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    # 5 ragged prompts through 2 slots: finished slots evict and the queue
    # splices new requests mid-flight (continuous batching)
    prompts = [_prompt(i, 3 + (2 * i) % 5) for i in range(5)]
    ref, ref_sch = _oracle_run(cfg, params, prompts, 5)
    assert ref_sch.stats.admissions == 5 and ref_sch.stats.evictions == 5

    ex = Executor(params, cfg, backend_cls(), mesh)
    outs, stats = ex.serve(prompts, max_new=5, slots=2, cache_len=32, seed=100)
    assert outs == ref, f"sharded {backend_cls.__name__} diverged from oracle"
    assert stats.admissions == 5 and stats.evictions == 5
    assert (stats.data_shards, stats.model_shards) == (2, 4)
    (sch,) = ex._schedulers.values()
    assert sch._decode._cache_size() == 1, "sharded decode_step recompiled"


@needs_mesh
@pytest.mark.parametrize("backend_cls", [IntegerBackend, PallasBackend])
def test_sharded_paged_scheduler_bit_exact(spiking_setup, mesh, backend_cls):
    """Block-paged serving on the (2, 4) mesh — page pool with KV heads
    sharded over ``model``, page tables/slots over ``data``, chunked
    prefill riding the sharded step — decodes the *dense* single-device
    integer oracle's tokens bit-for-bit, including a shared-prefix pair
    that hits the prefix cache."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    prompts = [_prompt(i, 3 + (2 * i) % 5) for i in range(5)]
    ref, _ = _oracle_run(cfg, params, prompts, 5)

    ex = Executor(params, cfg, backend_cls(), mesh)
    outs, stats = ex.serve(prompts, max_new=5, slots=2, cache_len=32,
                           seed=100, paged=True, page_len=8)
    assert outs == ref, f"mesh paged {backend_cls.__name__} diverged"
    sch = ex.scheduler(slots=2, cache_len=32, paged=True, page_len=8)
    assert sch._decode._cache_size() == 1, "mesh paged decode recompiled"

    # shared-prefix pair: second serve hits the pages the first registered
    shared = _prompt(9, 17)
    ref1, _ = _oracle_run(cfg, params, [shared], 4, seed0=1)
    ref2, _ = _oracle_run(cfg, params, [shared], 4, seed0=2)
    sch = ex.scheduler(slots=2, cache_len=32, paged=True, page_len=8)
    r1 = sch.submit(shared, 4, seed=1)
    o1 = dict(sch.run())
    r2 = sch.submit(shared, 4, seed=2)
    o2 = dict(sch.run())
    assert [o1[r1]] == ref1 and [o2[r2]] == ref2
    assert sch.stats.prefix_hit_tokens == 16, "prefix cache must hit on mesh"

    # a partial-tail hit: the second serve copy-on-writes the shared tail
    # page through the placed helpers, which take the sharded state
    # donated — a state kept across a helper call is deleted, not stale
    tail = _prompt(9, 14)  # n_ctx=13: one full 8-block + a 5-position tail
    ref3, _ = _oracle_run(cfg, params, [tail], 4, seed0=3)
    ref4, _ = _oracle_run(cfg, params, [tail], 4, seed0=4)
    sch = ex.scheduler(slots=2, cache_len=32, paged=True, page_len=8)
    r3 = sch.submit(tail, 4, seed=3)
    o3 = dict(sch.run())
    stale = sch.state
    r4 = sch.submit(tail, 4, seed=4)
    o4 = dict(sch.run())
    assert [o3[r3]] == ref3 and [o4[r4]] == ref4
    assert sch.stats.cow_copies >= 1, "the tail hit must copy-on-write"
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(stale.pool))
    for fn in (sch._admit_slot, sch._release_slot, sch._set_entry,
               sch._zero_pages_fn, sch._copy_page):
        assert fn._cache_size() == 1, "a placed page helper recompiled"


@needs_mesh
@pytest.mark.parametrize("backend_cls", [IntegerBackend, PallasBackend])
def test_sharded_fused_decode_head_parallel(spiking_setup, mesh, backend_cls):
    """``decode_kernel='fused'`` on the (2, 4) mesh: the megakernel's
    attention stage runs head-parallel inside shard_map (per-shard global
    ``h0`` offsets, column-sliced Q/K/V), the FFN tail rides the row/col-
    parallel spiking linears — and the whole serve decodes the
    single-device *unfused* integer oracle's tokens bit-for-bit, dense and
    paged, with exactly one decode compile."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    prompts = [_prompt(i, 3 + (2 * i) % 5) for i in range(4)]
    ref, _ = _oracle_run(cfg, params, prompts, 5)

    ex = Executor(params, cfg, backend_cls(), mesh)
    outs, stats = ex.serve(prompts, max_new=5, slots=2, cache_len=32,
                           seed=100, decode_kernel="fused")
    assert outs == ref, f"sharded fused {backend_cls.__name__} diverged"
    assert (stats.data_shards, stats.model_shards) == (2, 4)
    sch = ex.scheduler(slots=2, cache_len=32, decode_kernel="fused")
    assert sch.plan.fused
    assert sch._decode._cache_size() == 1, "sharded fused decode recompiled"

    # the paged megakernel rides the same head-parallel shard over the pool
    pouts, _ = ex.serve(prompts, max_new=5, slots=2, cache_len=32, seed=100,
                        paged=True, page_len=8, decode_kernel="fused")
    assert pouts == ref, f"sharded paged fused {backend_cls.__name__} diverged"


@needs_mesh
def test_sharded_preemption_matches_single_device(spiking_setup, mesh):
    """Explicit mid-run eviction with requeue (preemption) replays the same
    way sharded and unsharded."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    prompts = [_prompt(i, 4 + i) for i in range(3)]
    ref, _ = _oracle_run(cfg, params, prompts, 4, evict_after=2)

    ex = Executor(params, cfg, IntegerBackend(), mesh)
    sch = ex.scheduler(slots=2, cache_len=32)
    rids = [sch.submit(p, 4, seed=100 + i) for i, p in enumerate(prompts)]
    sch.step()
    sch.step()
    sch.evict(0, requeue=True)
    outs = sch.run()
    assert [outs[r] for r in rids] == ref


@needs_mesh
def test_dp_only_mesh_and_ann_arch(spiking_setup, mesh):
    """Data-parallel-only placement: an (8, 1) mesh for the spiking arch
    and the (2, 4) mesh for an ANN arch (params replicated, slots sharded)
    both reproduce single-device serving."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    prompts = [_prompt(i, 4) for i in range(4)]
    ref, _ = _oracle_run(cfg, params, prompts, 4, slots=4)
    dp_mesh = make_serving_mesh((8, 1))
    ex = Executor(params, cfg, IntegerBackend(), dp_mesh)
    outs, stats = ex.serve(prompts, max_new=4, slots=4, cache_len=32, seed=100)
    assert outs == ref
    assert stats.data_shards == 8 and stats.model_shards == 1

    acfg = reduced_config(ANN)
    aparams = T.init_params(jax.random.PRNGKey(0), acfg)
    sch = BatchScheduler(aparams, acfg, None, slots=4, cache_len=32)
    rids = [sch.submit(p, 4, seed=100 + i) for i, p in enumerate(prompts)]
    aref = [sch.run()[r] for r in rids]
    ex2 = Executor(aparams, acfg, None, mesh)
    aouts, _ = ex2.serve(prompts, max_new=4, slots=4, cache_len=32, seed=100)
    assert aouts == aref


# ---------------------------------------------------------------------------
# Programmed AIMC: drift + GDC on the mesh
# ---------------------------------------------------------------------------


@needs_mesh
def test_sharded_programmed_drift_gdc_bit_exact(spiking_setup, mesh):
    """The programmed-PCM lifecycle on the mesh — per-step clock advance,
    image refolds, periodic GDC recalibration (integer-sum calibration
    reads) — serves bit-identically to the single-device oracle and never
    recompiles the decode step."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    acfg = AD.AIMCConfig(drift_nu_sigma=0.005, prog_noise_sigma=0.01)
    hw = AD.program_lm_tree(jax.random.PRNGKey(42), params, acfg)
    pol = AD.DriftPolicy(seconds_per_step=600.0, recal_interval_s=2400.0,
                         cfg=acfg)
    prompts = [_prompt(i, 3 + i) for i in range(4)]
    ref, ref_sch = _oracle_run(cfg, hw, prompts, 6, seed0=10, drift=pol)
    assert ref_sch.stats.recalibrations >= 2

    ex = Executor(hw, cfg, IntegerBackend(), mesh)
    outs, stats = ex.serve(prompts, max_new=6, slots=2, cache_len=32, seed=10,
                           drift=pol)
    assert outs == ref
    assert stats.recalibrations == ref_sch.stats.recalibrations
    assert stats.t_device_s == ref_sch.stats.t_device_s
    assert stats.energy_j > 0 and abs(stats.energy_j - ref_sch.stats.energy_j) \
        <= 1e-9 * max(stats.energy_j, 1.0)
    (sch,) = ex._schedulers.values()
    assert sch._decode._cache_size() == 1, \
        "drift/GDC lifecycle recompiled the sharded decode_step"


@needs_mesh
def test_recalibrate_is_sharding_invariant(spiking_setup, mesh):
    """The GDC calibration read (integer image sums) measures the exact
    same gain on sharded and replicated device state."""
    from repro.distributed import param_pspecs_for_tree

    cfg, params = spiking_setup
    acfg = AD.AIMCConfig()
    hw = AD.program_lm_tree(jax.random.PRNGKey(1), params, acfg)
    hw = AD.drift_tree(hw, 86400.0, acfg)
    ref = AD.recalibrate_tree(hw, acfg)

    specs = param_pspecs_for_tree(cfg, hw, mesh)
    hw_sharded = jax.device_put(hw, SH.to_shardings(specs, mesh))
    got = AD.recalibrate_tree_jit(hw_sharded, acfg)

    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Placement rules
# ---------------------------------------------------------------------------


@needs_mesh
def test_param_pspecs_for_tree_device_state(spiking_setup, mesh):
    """Programmed leaves get per-field crossbar-view specs: Q/K/V/MLP-in
    column-sharded, attention-out/MLP-out row-sharded, scalars replicated."""
    from repro.distributed import param_pspecs_for_tree

    cfg, params = spiking_setup
    hw = AD.program_lm_tree(jax.random.PRNGKey(1), params, AD.AIMCConfig())
    specs = param_pspecs_for_tree(cfg, hw, mesh)
    blk = specs["periods"]["blk0"]
    wq, wo = blk["mixer"]["wq"], blk["mixer"]["wo"]
    # stacked period leaves: [layers, d_in, d_out]
    assert tuple(wq.levels_t) == (None, None, "model")
    assert tuple(wq.scale) == (None, "model")
    assert tuple(wo.levels_t) == (None, "model", None)
    assert tuple(wo.scale) == ()
    assert tuple(wq.t_seconds) == () and tuple(wq.gdc_gain) == ()
    mlp = blk["mlp"]
    assert tuple(mlp["wi"].levels_t) == (None, None, "model")
    assert tuple(mlp["wo"].levels_t) == (None, "model", None)


@needs_mesh
def test_cache_pspecs_shard_spiking_kv_heads(spiking_setup, mesh):
    """The spiking KV cache shards its head axis over ``model`` and the
    slot axis over ``data``; DecodeState vectors ride ``data``."""
    from repro.distributed import Executor

    cfg, params = spiking_setup
    cs = SH.cache_pspecs(cfg, mesh, 4, 32)
    sk = cs["periods"]["blk0"]["sk"]
    # [layers, B, spike_T, L, KV, hd]
    assert tuple(sk) == (None, "data", None, None, "model", None)
    ex = Executor(params, cfg, IntegerBackend(), mesh)
    ss = ex.state_specs(4, 32)
    assert tuple(ss.tokens) == ("data",)
    assert tuple(ss.seeds) == ("data",)


def test_parse_mesh_spec():
    assert parse_mesh_spec("2x4") == (2, 4)
    assert parse_mesh_spec("2,4") == (2, 4)
    assert parse_mesh_spec("4") == (4, 1)
    assert parse_mesh_spec("auto")[1] == 1
    with pytest.raises(ValueError):
        parse_mesh_spec("2x2x2")


# ---------------------------------------------------------------------------
# Shard-local kernel ops
# ---------------------------------------------------------------------------


def test_aimc_matmul_counts_matches_ref(rng):
    """The counts kernel (shard-local programmed-AIMC matmul) == oracle."""
    from repro.kernels import ops as KOPS
    from repro.kernels import ref as KREF

    k1, k2 = jax.random.split(rng)
    spikes = jax.random.bernoulli(k1, 0.4, (3, 5, 48)).astype(jnp.float32)
    levels = jax.random.randint(k2, (48, 33), -15, 16, jnp.int32).astype(jnp.int8)
    got = KOPS.aimc_matmul_counts(spikes, levels)
    ref = KREF.aimc_counts_ref(spikes, levels)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_per_head_decode_prns_offset_slices():
    """A shard drawing heads [h0, h0+h) gets exactly the oracle's rows for
    those heads — the f(seed, pos, head) stream contract of TP decode."""
    from repro.kernels import ops as KOPS

    keys = jnp.asarray([[0, 5], [0, 9]], jnp.uint32)
    t, h, l, d, i_max = 3, 4, 16, 8, 16
    rs, ra = KOPS.draw_slot_decode_prns(keys, t, h, l, d, i_max)
    rs2, ra2 = KOPS.draw_slot_decode_prns(keys, t, 2, l, d, i_max, h0=2)
    full = rs.reshape(2, t, h, 1, l)[:, :, 2:4]
    shard = rs2.reshape(2, t, 2, 1, l)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(shard))
    full_a = ra.reshape(2, t, h, 1, d)[:, :, 2:4]
    shard_a = ra2.reshape(2, t, 2, 1, d)
    np.testing.assert_array_equal(np.asarray(full_a), np.asarray(shard_a))
