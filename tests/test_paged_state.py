"""Slot/page surgery: DecodeState + PagedDecodeState state management.

Direct unit coverage of the serving-state primitives (satellite of the
paged-KV-cache PR):

* eviction really releases state — paged slots refcount-release their
  pages and freed pages are zeroed on device (spiking comparators see
  nothing); dense ANN slots become unreachable via ``pos = 0``;
* splice round-trips through both cache stackings (``periods`` scan leaves
  and unrolled ``remainder`` leaves);
* the page economics guard rails: double-free, use-after-free retain,
  evicting an unoccupied slot, and foreign-page release all raise;
* pool page copy (copy-on-write) keeps exactly the valid prefix;
* the jitted page helpers take the paged state donated: they edit it in
  place (the result lives in the input's buffers, the input is deleted)
  and compute exactly what the undonated helper computes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import reduced_config
from repro.engine import IntegerBackend
from repro.models import transformer as T
from repro.serving import (NULL_PAGE, RESERVED_PAGES, TRASH_PAGE,
                           BatchScheduler, PagePool, init_paged_state,
                           init_state, paged_admit_slot, paged_release_slot,
                           paged_set_table_entry, pool_copy_page,
                           pool_zero_pages, release_slot, slot_slice,
                           splice_request)
from repro.serving import state as ST

SPIKING = "xpikeformer-gpt-4-256"
ANN = "yi-9b"


def _pages_first(leaf) -> np.ndarray:
    """Pool leaf -> numpy with the physical-page axis leading (the page
    axis sits at -5 in both the periods and remainder stackings)."""
    return np.moveaxis(np.asarray(leaf), -5, 0)


@pytest.fixture(scope="module")
def spiking_cfg():
    return reduced_config(SPIKING)


@pytest.fixture(scope="module")
def remainder_cfg():
    """A spiking SSA config whose depth does not divide its period, so the
    cache carries BOTH stackings: scan-stacked ``periods`` leaves and
    unrolled ``remainder`` leaves."""
    base = reduced_config(SPIKING)
    cfg = dataclasses.replace(base, name="xpike-remainder-smoke",
                              block_pattern=("attn", "attn"), num_layers=3)
    cfg = cfg.validate()
    assert cfg.num_periods == 1 and cfg.remainder_layers == 1
    return cfg


# ---------------------------------------------------------------------------
# Dense slot surgery
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [SPIKING, ANN])
def test_dense_eviction_releases_state(arch):
    """Evicted dense slots are zeroed: spiking KV trains read as no spikes,
    ANN caches make stale keys unreachable (``pos = 0``)."""
    cfg = reduced_config(arch)
    st = init_state(cfg, 2, 16)
    one = jax.tree.map(lambda a: jnp.ones_like(a), T.init_cache(cfg, 1, 16))
    st = splice_request(st, 1, one, jnp.int32(7), jnp.uint32(3))
    st = release_slot(st, 1)
    assert not bool(st.active[1])
    for leaf in jax.tree.leaves(slot_slice(st.cache, 1)):
        assert float(jnp.abs(leaf.astype(jnp.float32)).sum()) == 0.0, \
            "evicted slot retains cache state"


def test_dense_splice_roundtrips_both_stackings(remainder_cfg):
    """slot_splice/slot_slice invert through periods AND remainder leaves."""
    cfg = remainder_cfg
    st = init_state(cfg, 3, 16)
    one = T.init_cache(cfg, 1, 16)
    one = jax.tree.map(
        lambda a: (jnp.arange(a.size, dtype=jnp.float32) % 2).reshape(a.shape
                                                                      ).astype(a.dtype), one)
    assert "periods" in one and "remainder" in one
    st = splice_request(st, 2, one, jnp.int32(5), jnp.uint32(9))
    got = slot_slice(st.cache, 2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b.astype(a.dtype)))
    # other slots untouched
    for leaf in jax.tree.leaves(slot_slice(st.cache, 0)):
        assert float(jnp.abs(leaf.astype(jnp.float32)).sum()) == 0.0


# ---------------------------------------------------------------------------
# Paged slot surgery
# ---------------------------------------------------------------------------


def test_paged_pool_schema_covers_both_stackings(remainder_cfg):
    pool = T.init_paged_pool(remainder_cfg, 6, 8)
    assert "periods" in pool and "remainder" in pool
    assert pool["periods"]["blk0"]["kp"].ndim == 6  # [layers, P, T, KV, pl, hd]
    assert pool["remainder"]["blk0"]["kp"].ndim == 5


def test_paged_eviction_releases_and_zeroes_pages(spiking_cfg):
    """Through a real scheduler run: after eviction the slot's exclusive
    pages return to the free list zeroed; prefix-cached pages survive
    exactly once each."""
    cfg = spiking_cfg
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    sch = BatchScheduler(params, cfg, IntegerBackend(), slots=2, cache_len=32,
                         paged=True, page_len=8)
    rid = sch.submit(list(range(3, 14)), 3, seed=1)  # n_ctx=10: 2 pages
    sch.run()
    # slot released: only prefix-cache references may remain
    live = sch.pages.refcount[RESERVED_PAGES:]
    assert int((live > 0).sum()) == sch.pages.prefix_len()
    assert (live <= 1).all(), "evicted slot left extra page references"
    # every free page is zeroed on device
    table = np.asarray(sch.state.page_table)
    assert (table == NULL_PAGE).all()
    free_mask = np.ones(sch.n_pages, bool)
    free_mask[:RESERVED_PAGES] = False
    free_mask[np.asarray(sch.pages.refcount) > 0] = False
    for leaf in jax.tree.leaves(sch.state.pool):
        arr = _pages_first(leaf)
        assert arr[free_mask].sum() == 0, "freed page not zeroed"
    assert len(sch.outputs[rid]) == 3


def test_paged_admit_release_roundtrip(spiking_cfg):
    st = init_paged_state(spiking_cfg, 2, 32, 8, 10)
    row = jnp.asarray([3, 4, NULL_PAGE, NULL_PAGE], jnp.int32)
    st = paged_admit_slot(st, 1, row, jnp.uint32(7), jnp.int32(16))
    assert bool(st.active[1]) and int(st.pos[1]) == 16
    np.testing.assert_array_equal(np.asarray(st.page_table[1]), np.asarray(row))
    st = paged_release_slot(st, 1)
    assert not bool(st.active[1]) and int(st.pos[1]) == 0
    assert (np.asarray(st.page_table[1]) == NULL_PAGE).all()


def test_pool_copy_page_keeps_valid_prefix(spiking_cfg):
    """Copy-on-write semantics: the copy carries in-page positions below
    ``keep_upto`` and zeros above (the new owner's unwritten tail must stay
    comparator-masked)."""
    st = init_paged_state(spiking_cfg, 1, 32, 8, 6)
    ones = jax.tree.map(lambda a: jnp.ones_like(a), st.pool)
    st = dataclasses.replace(st, pool=ones)
    st = pool_copy_page(st, jnp.int32(3), jnp.int32(4), jnp.int32(5))
    for leaf in jax.tree.leaves(st.pool):
        arr = _pages_first(leaf)  # [P, ..., page_len, hd]
        assert (arr[4, ..., :5, :] == 1).all(), "valid prefix lost in CoW"
        assert (arr[4, ..., 5:, :] == 0).all(), "CoW leaked the stale tail"
        assert (arr[3] == 1).all(), "CoW touched the source page"


def test_pool_zero_pages(spiking_cfg):
    st = init_paged_state(spiking_cfg, 1, 32, 8, 6)
    st = dataclasses.replace(
        st, pool=jax.tree.map(lambda a: jnp.ones_like(a), st.pool))
    st = pool_zero_pages(st, jnp.asarray([2, 5], jnp.int32))
    for leaf in jax.tree.leaves(st.pool):
        arr = _pages_first(leaf)
        assert arr[[2, 5]].sum() == 0 and (arr[[3, 4]] == 1).all()


def _row(*pids):
    return jnp.asarray(list(pids) + [NULL_PAGE] * (4 - len(pids)), jnp.int32)


# (plain helper, its donating jit, the arguments after the state)
DONATED_HELPERS = {
    "admit_slot": (paged_admit_slot, ST.paged_admit_slot_jit,
                   (jnp.int32(1), _row(6, 7, 8), jnp.uint32(9),
                    jnp.int32(16))),
    "release_slot": (paged_release_slot, ST.paged_release_slot_jit,
                     (jnp.int32(0),)),
    "set_table_entry": (paged_set_table_entry, ST.paged_set_table_entry_jit,
                        (jnp.int32(0), jnp.int32(2), jnp.int32(5))),
    "zero_pages": (pool_zero_pages, ST.pool_zero_pages_jit,
                   (jnp.asarray([3, 4, TRASH_PAGE, TRASH_PAGE], jnp.int32),)),
    "copy_page": (pool_copy_page, ST.pool_copy_page_jit,
                  (jnp.int32(3), jnp.int32(9), jnp.int32(5))),
}


@pytest.mark.parametrize("name", sorted(DONATED_HELPERS))
def test_page_helper_donates_state(remainder_cfg, name):
    """Each jitted page helper consumes its state argument: the result's
    pool leaves live in the input's buffers (an in-place edit, not a copy
    of the pool), every input leaf is deleted, and the result equals the
    undonated helper's on a copy — through both cache stackings."""
    plain, donating, args = DONATED_HELPERS[name]
    st = init_paged_state(remainder_cfg, 2, 32, 8, 10)
    pool = jax.tree.map(
        lambda a: jax.random.bernoulli(jax.random.PRNGKey(a.ndim), 0.5,
                                       a.shape).astype(a.dtype), st.pool)
    st = paged_admit_slot(dataclasses.replace(st, pool=pool), 0,
                          _row(3, 4), jnp.uint32(2), jnp.int32(11))
    want = plain(jax.tree.map(jnp.copy, st), *args)
    ptrs = [leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(st.pool)]
    got = donating(st, *args)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(st)), \
        "the helper's state argument was not donated"
    assert [leaf.unsafe_buffer_pointer()
            for leaf in jax.tree.leaves(got.pool)] == ptrs, \
        "the pool was copied instead of edited in place"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# PagePool guard rails
# ---------------------------------------------------------------------------


def test_pagepool_double_free_raises():
    pool = PagePool(8, 8)
    pid = pool.alloc()
    assert pool.release(pid) is True
    with pytest.raises(ValueError, match="double free"):
        pool.release(pid)


def test_pagepool_use_after_free_raises():
    pool = PagePool(8, 8)
    pid = pool.alloc()
    pool.release(pid)
    with pytest.raises(ValueError, match="use-after-free"):
        pool.retain(pid)


def test_pagepool_reserved_pages_are_immortal():
    pool = PagePool(8, 8)
    for pid in (NULL_PAGE, TRASH_PAGE):
        with pytest.raises(ValueError):
            pool.release(pid)
        with pytest.raises(ValueError):
            pool.retain(pid)


def test_pagepool_reservations_gate_alloc():
    pool = PagePool(RESERVED_PAGES + 3, 8)
    pool.reserve(2)
    assert pool.available() == 1
    with pytest.raises(RuntimeError, match="reservation"):
        pool.reserve(2)
    a = pool.alloc(reserved=True)
    assert pool.available() == 1 and pool.free_pages == 2
    pool.release(a)


def test_pagepool_prefix_cache_lru_and_refcounts():
    pool = PagePool(RESERVED_PAGES + 4, 8)
    pids = [pool.alloc() for _ in range(3)]
    chains = []
    for i, pid in enumerate(pids):
        chains.append(pool.prefix_register(("k", i), pid, chain=True))
        pool.release(pid)  # slot drops its ref; cache keeps the page alive
    assert pool.free_pages == 1
    assert chains == sorted(chains) and len(set(chains)) == 3  # fresh ids
    hit = pool.prefix_lookup(("k", 0))  # refreshes LRU position
    assert hit == (pids[0], chains[0])
    # re-registering an existing key retains nothing, returns the canonical id
    assert pool.prefix_register(("k", 0), pids[0], chain=True) == chains[0]
    pool.release(pids[0])
    # eviction walks LRU: entry 1 is now the oldest
    freed = pool.prefix_evict(1)
    assert freed == [pids[1]]
    assert not pool.prefix_contains(("k", 1)) and pool.prefix_contains(("k", 0))


def test_scheduler_evict_unoccupied_slot_raises(spiking_cfg):
    params = T.init_params(jax.random.PRNGKey(0), spiking_cfg)
    for paged in (False, True):
        sch = BatchScheduler(params, spiking_cfg, IntegerBackend(), slots=2,
                             cache_len=32, paged=paged, page_len=8)
        rid = sch.submit([3, 4, 5], 2, seed=0)
        sch.run()
        assert len(sch.outputs[rid]) == 2
        with pytest.raises(ValueError, match="use-after-evict"):
            sch.evict(0)  # the run already evicted the finished slot