"""Block-paged spike-train KV cache: paged serving == dense serving, bitwise.

The paged-serving contracts (see ``repro/serving``):

* **paged == dense, bit-exact** — a full ``BatchScheduler`` run off the
  block-paged pool (chunked prefill riding the batched step, page
  allocation at block boundaries, copy-on-write off shared pages,
  admissions and evictions mid-flight) decodes exactly the tokens of the
  dense single-device integer oracle, on every bit-exact substrate.
* **exact prefix reuse** — prefill PRN streams are content-keyed
  (``state.content_keys``), so identical prompt prefixes produce
  bit-identical spike trains; the prefix cache maps them onto the *same
  physical pages* and the skipped prefill provably changes nothing about
  the generated tokens.
* **page accounting** — refcounts, reservations, the LRU prefix cache and
  copy-on-write never leak or double-free pages; admission blocks on free
  pages, not free slots.  A pure-Python oracle scheduler replays random
  submit/step/evict/preempt traces and must agree with the real scheduler
  on slot occupancy, page refcounts, completion sets and ``ServeStats``
  token accounting at every step.
* **drift + GDC** — programmed-PCM execution (drifted and recalibrated
  device state) serves identically paged and dense, and the drift policy
  lifecycle never recompiles the single jitted paged step.
"""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from repro import aimc_device as AD
from repro.configs.registry import reduced_config
from repro.engine import IntegerBackend, get_backend
from repro.models import transformer as T
from repro.serving import BatchScheduler

SPIKING = "xpikeformer-gpt-4-256"


@pytest.fixture(scope="module")
def spiking_setup():
    cfg = reduced_config(SPIKING)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(i, length):
    return list(range(3 + i, 3 + i + length))


def _run(sch, prompts, max_new, seed0=100):
    rids = [sch.submit(p, max_new, seed=seed0 + i)
            for i, p in enumerate(prompts)]
    outs = sch.run()
    return [outs[r] for r in rids]


# ---------------------------------------------------------------------------
# Paged == dense (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_paged_matches_dense_full_run(spiking_setup, engine_backend):
    """Ragged prompts through fewer slots than requests — admissions,
    evictions and chunked prefill all engaged — decode the dense
    scheduler's exact tokens on the CI-matrix backend."""
    cfg, params = spiking_setup
    be = get_backend(engine_backend)
    prompts = [_prompt(i, 3 + (3 * i) % 7) for i in range(5)]
    dense = BatchScheduler(params, cfg, be, slots=2, cache_len=32)
    ref = _run(dense, prompts, 5)
    paged = BatchScheduler(params, cfg, be, slots=2, cache_len=32,
                           paged=True, page_len=8)
    got = _run(paged, prompts, 5)
    if be.bit_exact:  # integer/pallas: exact; reference floats may
        assert got == ref, "paged serving diverged from the dense scheduler"
    else:  # reassociate across the different prefill batch shapes
        assert [len(o) for o in got] == [len(o) for o in ref]
        assert all(0 <= t < cfg.vocab_size for o in got for t in o)
    assert paged.stats.admissions == 5 and paged.stats.evictions == 5
    assert paged.stats.prefill_tokens == sum(len(p) - 1 for p in prompts)
    assert paged._decode._cache_size() == 1, "paged decode_step recompiled"


def test_paged_pallas_bit_exact_vs_dense_integer_oracle(spiking_setup):
    """The paged popcount kernel path (scalar-prefetch page gathering)
    serves bit-identically to the *dense integer oracle* through the whole
    scheduler — kernels, paging and scheduling all in the loop."""
    from repro.engine import PallasBackend

    cfg, params = spiking_setup
    prompts = [_prompt(i, 4 + i) for i in range(4)]
    ref = _run(BatchScheduler(params, cfg, IntegerBackend(), slots=2,
                              cache_len=32), prompts, 4)
    got = _run(BatchScheduler(params, cfg, PallasBackend(), slots=2,
                              cache_len=32, paged=True, page_len=8),
               prompts, 4)
    assert got == ref


def test_prefix_cache_hit_is_exact_and_skips_prefill(spiking_setup,
                                                     engine_backend):
    """A second request with an identical prompt verifiably hits the
    prefix cache (full blocks + the partial tail), skips its whole-context
    prefill, and still generates exactly the dense scheduler's tokens."""
    cfg, params = spiking_setup
    be = get_backend(engine_backend)
    shared = _prompt(7, 17)  # n_ctx=16: two full 8-blocks at page_len=8
    dense = BatchScheduler(params, cfg, be, slots=2, cache_len=32)
    paged = BatchScheduler(params, cfg, be, slots=2, cache_len=32,
                           paged=True, page_len=8)
    ref1 = _run(dense, [shared], 4, seed0=1)
    dense.reset()
    ref2 = _run(dense, [shared], 4, seed0=2)

    got1 = _run(paged, [shared], 4, seed0=1)  # cold: fills + registers pages
    assert paged.stats.prefix_hit_tokens == 0
    got2 = _run(paged, [shared], 4, seed0=2)  # warm: same prompt, new seed
    if get_backend(engine_backend).bit_exact:
        assert got1 == ref1 and got2 == ref2
    else:
        assert [len(o) for o in got1 + got2] == [4, 4]
    st = paged.stats
    assert st.prefix_hit_tokens == 16, "second request must reuse all blocks"
    assert st.prefix_hits == 2
    # prefill compute really was skipped: only the cold request prefilled
    assert st.prefill_tokens == 16
    assert paged.pages.prefix_hits == 2 and paged.pages.prefix_misses >= 1


def test_partial_block_hit_triggers_copy_on_write(spiking_setup):
    """A shared *partial* tail block is served copy-on-write: the hitting
    request gets a private copy before its first decode write, the cached
    page stays pristine for future hits, and tokens stay bit-exact."""
    cfg, params = spiking_setup
    shared = _prompt(3, 6)  # n_ctx=5: one partial block at page_len=8
    dense = BatchScheduler(params, cfg, IntegerBackend(), slots=2, cache_len=32)
    paged = BatchScheduler(params, cfg, IntegerBackend(), slots=2,
                           cache_len=32, paged=True, page_len=8)
    refs = []
    for seed0 in (1, 2, 3):
        dense.reset()
        refs.append(_run(dense, [shared], 3, seed0=seed0))
    outs = [_run(paged, [shared], 3, seed0=s) for s in (1, 2, 3)]
    assert outs == refs
    st = paged.stats
    # request 1 CoWs off its own registered tail page; 2 and 3 CoW off the
    # cache's pristine page at admission
    assert st.cow_copies >= 3
    assert st.prefix_hit_tokens == 10  # 5 skipped context tokens, twice


def test_admission_blocks_on_free_pages_not_slots(spiking_setup):
    """With plenty of slots but a tiny pool, admission queues requests on
    page pressure and serves them as pages free — and the served tokens
    still match the dense scheduler."""
    cfg, params = spiking_setup
    prompts = [_prompt(i, 9) for i in range(4)]  # 2 pages per request
    dense = BatchScheduler(params, cfg, IntegerBackend(), slots=4, cache_len=16)
    ref = _run(dense, prompts, 6)
    # 4 slots x 2 blocks would want 8 pages; give the pool 4 usable
    paged = BatchScheduler(params, cfg, IntegerBackend(), slots=4,
                           cache_len=16, paged=True, page_len=8, n_pages=6)
    got = _run(paged, prompts, 6)
    assert got == ref
    st = paged.stats
    assert st.pages_in_use_peak <= 4, "pool over-committed"
    assert st.peak_active_slots < 4, "page pressure should gate admission"
    assert st.admissions == 4, "queued requests must still serve eventually"


def test_failed_admission_zeroes_last_ref_prefix_pages(spiking_setup):
    """Regression: when admission retains prefix-hit pages, then pool
    pressure LRU-drops those very cache entries, the failure path's
    release is the page's LAST ref — it must be zeroed before reuse, or a
    later slot reads phantom stale spike trains through the null-page
    invariant."""
    cfg, params = spiking_setup
    shared = _prompt(7, 17)  # 2 full blocks at page_len=8
    other = _prompt(40, 9)  # 1 block, disjoint tokens
    dense = BatchScheduler(params, cfg, IntegerBackend(), slots=2, cache_len=32)
    ref_b = _run(dense, [shared], 3, seed0=5)
    paged = BatchScheduler(params, cfg, IntegerBackend(), slots=2,
                           cache_len=32, paged=True, page_len=8, n_pages=6)
    _run(paged, [shared], 3, seed0=1)  # registers 2 prefix pages
    assert paged.pages.prefix_len() == 2
    # occupy the pool: 'other' reserves/allocates its 2 pages...
    paged.submit(other, 8, seed=2)
    paged.step()
    # ...then the shared-prefix request can't reserve (hits retained, then
    # the LRU eviction drops exactly the hit entries, freeing nothing)
    rb = paged.submit(shared, 3, seed=5)
    paged.step()
    assert paged.pages.prefix_len() == 0, "pressure must drop LRU entries"
    from repro.serving import NULL_PAGE, RESERVED_PAGES

    occupied = {int(p) for p in paged._table_rows.ravel() if p != NULL_PAGE}
    for leaf in jax.tree.leaves(paged.state.pool):
        arr = np.moveaxis(np.asarray(leaf), -5, 0)
        for pid in range(RESERVED_PAGES, paged.n_pages):
            if pid not in occupied:
                assert arr[pid].sum() == 0, f"freed page {pid} not zeroed"
    outs = paged.run()  # 'other' drains, then the shared request serves
    assert outs[rb] == ref_b[0], "request served off a dirty recycled page"


def test_paged_preemption_requeue_matches_dense(spiking_setup):
    """Mid-flight eviction with requeue (preemption) replays the same way
    paged and dense; the preempted request's pages are released."""
    cfg, params = spiking_setup
    prompts = [_prompt(i, 4 + i) for i in range(3)]

    def run_with_preempt(sch):
        rids = [sch.submit(p, 4, seed=100 + i) for i, p in enumerate(prompts)]
        for _ in range(2):
            sch.step()
        sch.evict(0, requeue=True)
        outs = sch.run()
        return [outs[r] for r in rids]

    ref = run_with_preempt(
        BatchScheduler(params, cfg, IntegerBackend(), slots=2, cache_len=32))
    paged = BatchScheduler(params, cfg, IntegerBackend(), slots=2,
                           cache_len=32, paged=True, page_len=8)
    got = run_with_preempt(paged)
    assert got == ref
    # all slot references released at drain (only cache entries may remain)
    live = paged.pages.refcount[2:]
    assert int(live.sum()) == paged.pages.prefix_len()


def test_engine_serve_paged_api(spiking_setup):
    """engine.serve(paged=True) wires the paged geometry through and
    matches its own dense serve on the integer substrate."""
    from repro.engine import XpikeformerEngine

    cfg, params = spiking_setup
    eng = XpikeformerEngine.from_config(cfg, backend="integer")
    eng.params = params
    prompts = [_prompt(0, 4), _prompt(1, 6)]
    ref, _ = eng.serve(prompts, max_new=4, slots=2, cache_len=32)
    got, st = eng.serve(prompts, max_new=4, slots=2, cache_len=32,
                        paged=True, page_len=8)
    assert got == ref
    assert st.pages_in_use_peak > 0


def test_paged_rejects_unsupported_arch():
    """ANN / recurrent-state archs have no position axis to page."""
    cfg = reduced_config("yi-9b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="paged serving"):
        BatchScheduler(params, cfg, None, slots=2, cache_len=32, paged=True)


def test_oversized_request_raises_at_submit(spiking_setup):
    cfg, params = spiking_setup
    sch = BatchScheduler(params, cfg, IntegerBackend(), slots=2, cache_len=32,
                         paged=True, page_len=8, n_pages=4)  # 2 usable pages
    with pytest.raises(ValueError, match="could never be admitted"):
        sch.submit(_prompt(0, 17), 8)


# ---------------------------------------------------------------------------
# Programmed PCM: drift + GDC through the paged path
# ---------------------------------------------------------------------------


def _programmed(spiking_setup):
    cfg, params = spiking_setup
    acfg = AD.AIMCConfig(drift_nu_sigma=0.005, prog_noise_sigma=0.01)
    return cfg, AD.program_lm_tree(jax.random.PRNGKey(42), params, acfg), acfg


def test_paged_programmed_drift_gdc_matches_dense(spiking_setup):
    """Programmed-PCM execution — fresh, day-drifted, and drifted+GDC
    device state — serves bit-identically paged and dense (the drift
    lifecycle is a pure param-leaf change, orthogonal to cache layout)."""
    cfg, hw, acfg = _programmed(spiking_setup)
    aged = AD.drift_tree(hw, 86400.0, acfg)
    recal = AD.recalibrate_tree(aged, acfg)
    prompts = [_prompt(i, 4 + i) for i in range(3)]
    for tree in (hw, aged, recal):
        ref = _run(BatchScheduler(tree, cfg, IntegerBackend(), slots=2,
                                  cache_len=32), prompts, 4)
        got = _run(BatchScheduler(tree, cfg, IntegerBackend(), slots=2,
                                  cache_len=32, paged=True, page_len=8),
                   prompts, 4)
        assert got == ref, "programmed paged serving diverged from dense"


def test_paged_drift_policy_soak(spiking_setup, engine_backend):
    """The DriftPolicy lifecycle (clock advance per step, periodic GDC)
    runs through the paged scheduler without recompiling the jitted step
    and keeps serving valid tokens."""
    cfg, hw, acfg = _programmed(spiking_setup)
    pol = AD.DriftPolicy(seconds_per_step=600.0, recal_interval_s=2400.0,
                         cfg=acfg)
    sch = BatchScheduler(hw, cfg, get_backend(engine_backend), slots=2,
                         cache_len=32, drift=pol, paged=True, page_len=8)
    rids = [sch.submit(_prompt(i, 3 + i), 6, seed=10 + i) for i in range(4)]
    outs = sch.run()
    st = sch.stats
    assert all(len(outs[r]) == 6 for r in rids)
    assert all(0 <= t < cfg.vocab_size for r in rids for t in outs[r])
    assert st.t_device_s == 600.0 * st.decode_steps
    assert st.recalibrations >= 2, "periodic GDC must have fired"
    assert sch._decode._cache_size() == 1, \
        "drift lifecycle must not recompile the paged decode_step"


# ---------------------------------------------------------------------------
# Donated page helpers through the whole scheduler
# ---------------------------------------------------------------------------


def test_donated_page_helpers_serve_exactly_across_reset(spiking_setup):
    """Every page helper takes the paged state donated.  A serve that
    crosses page boundaries, hits a shared partial tail (copy-on-write),
    finishes requests (table release, page zeroing) and is ``reset()``
    mid-life still decodes the dense scheduler's exact tokens, each helper
    and the decode step compiling once, and a state kept across a helper
    call is deleted rather than silently stale."""
    cfg, params = spiking_setup
    shared = _prompt(5, 14)  # n_ctx=13: one full 8-block + a 5-position tail
    mixes = [([shared, _prompt(1, 12), _prompt(2, 4)], 10),
             ([shared, _prompt(3, 9)], 20),  # full-block + tail hits: CoW
             ([shared, _prompt(1, 12), _prompt(2, 4)], 30)]  # after reset()
    dense = BatchScheduler(params, cfg, IntegerBackend(), slots=2, cache_len=32)
    paged = BatchScheduler(params, cfg, IntegerBackend(), slots=2,
                           cache_len=32, paged=True, page_len=8)
    # the helpers are module-level jits shared by every scheduler of the
    # process: count only the compiles this scheduler's shapes add
    helpers = (paged._admit_slot, paged._release_slot, paged._set_entry,
               paged._zero_pages_fn, paged._copy_page)
    compiled = [fn._cache_size() for fn in helpers]
    ref, got = [], []
    for i, (prompts, seed0) in enumerate(mixes):
        if i == 2:
            st = paged.stats
            assert st.prefix_hits >= 2 and st.cow_copies >= 1
            assert st.evictions == 5
            assert st.pool_writes > st.admissions + st.evictions
            dense.reset()
            paged.reset()
        ref.append(_run(dense, prompts, 6, seed0=seed0))
        got.append(_run(paged, prompts, 6, seed0=seed0))
    assert got == ref, "donated page helpers changed the served tokens"
    assert paged._decode._cache_size() == 1, "paged decode_step recompiled"
    for fn, before in zip(helpers, compiled):
        assert fn._cache_size() - before <= 1, "a page helper recompiled"

    stale = paged.state
    paged.submit(shared, 2, seed=40)
    paged.step()  # admission runs _admit_slot on the state
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(stale.pool))
    with pytest.raises(RuntimeError):
        np.asarray(stale.page_table)


# ---------------------------------------------------------------------------
# Scheduler trace oracle (pure-Python reference bookkeeping)
# ---------------------------------------------------------------------------


class _OraclePage:
    __slots__ = ("ref",)

    def __init__(self):
        self.ref = 1


class OracleScheduler:
    """A pure-Python model of the paged scheduler's *bookkeeping* — no
    jax, no model, no spike math.  It mirrors the admission / chunked
    prefill / eviction state machine and the page economics (reservations,
    refcounts, LRU prefix cache, copy-on-write) from the spec in
    ``repro/serving``, and is replayed op-for-op against the real
    ``BatchScheduler`` to pin the host accounting down."""

    def __init__(self, slots, cache_len, page_len, n_pages):
        self.slots, self.page_len = slots, page_len
        self.max_pages = cache_len // page_len
        self.usable = n_pages - 2  # null + trash
        self.free = self.usable
        self.reserved = 0
        # chained-block key -> (page, chain id | None); insertion = LRU
        self.cache = {}
        self.next_chain = 1
        self.queue = []  # (rid, prompt list, max_new)
        self.slot_req = [None] * slots
        self.table = [[None] * self.max_pages for _ in range(slots)]
        self.phase = ["decode"] * slots
        self.cursor = [0] * slots
        self.pos = [0] * slots
        self.remaining = [0] * slots
        self.slot_reserved = [0] * slots
        self.chain = [0] * slots
        self.done = {}  # rid -> n generated
        self.prefill_tokens = 0
        self.decoded_tokens = 0
        self.admissions = 0
        self.evictions = 0
        self.prefix_hit_tokens = 0

    # -- page economics -------------------------------------------------

    def _alloc(self, slot):
        assert self.free > 0
        self.free -= 1
        self.reserved -= 1
        self.slot_reserved[slot] -= 1
        return _OraclePage()

    def _release(self, page):
        page.ref -= 1
        assert page.ref >= 0
        if page.ref == 0:
            self.free += 1

    def _available(self):
        return self.free - self.reserved

    def _prefix_evict(self, need):
        freed = 0
        while freed < need and self.cache:
            key = next(iter(self.cache))
            page, _ = self.cache.pop(key)
            before = self.free
            self._release(page)
            freed += self.free - before

    # -- ops --------------------------------------------------------------

    def submit(self, rid, prompt, max_new):
        self.queue.append((rid, list(prompt), max_new))

    def admit(self):
        for slot in range(self.slots):
            if not self.queue or self.slot_req[slot] is not None:
                continue
            rid, prompt, max_new = self.queue[0]
            ctx = prompt[:-1]
            n_ctx = len(ctx)
            pl = self.page_len
            total = -(-(n_ctx + max_new) // pl)
            hits = []
            chain = 0
            k = pl
            while k <= n_ctx:
                key = (chain, tuple(ctx[k - pl:k]))
                ent = self.cache.get(key)
                if ent is None:
                    break
                self.cache[key] = self.cache.pop(key)  # LRU refresh
                ent[0].ref += 1
                hits.append(ent[0])
                chain = ent[1]
                k += pl
            partial = None
            if len(hits) == n_ctx // pl and n_ctx % pl:
                key = (chain, tuple(ctx[len(hits) * pl:]))
                ent = self.cache.get(key)
                if ent is not None:
                    self.cache[key] = self.cache.pop(key)
                    ent[0].ref += 1
                    partial = ent[0]
            needed = total - len(hits)
            if self._available() < needed:
                self._prefix_evict(needed - self._available())
            if self._available() < needed:
                for p in hits:
                    self._release(p)
                if partial is not None:
                    self._release(partial)
                return
            self.queue.pop(0)
            self.reserved += needed
            self.slot_reserved[slot] = needed
            row = [None] * self.max_pages
            for j, p in enumerate(hits):
                row[j] = p
            cursor = len(hits) * pl
            if partial is not None:
                row[n_ctx // pl] = partial
                cursor = n_ctx
            self.table[slot] = row
            self.slot_req[slot] = (rid, prompt, max_new)
            self.phase[slot] = "prefill" if cursor < n_ctx else "handoff"
            self.cursor[slot] = cursor
            self.pos[slot] = cursor
            self.remaining[slot] = max_new
            self.chain[slot] = chain
            self.done[rid] = 0
            self.prefix_hit_tokens += cursor
            self.admissions += 1

    def evict(self, slot, requeue=False):
        rid, prompt, max_new = self.slot_req[slot]
        if requeue:
            self.queue.insert(0, (rid, prompt, max_new))
            self.done.pop(rid, None)
        for page in self.table[slot]:
            if page is not None:
                self._release(page)
        self.reserved -= self.slot_reserved[slot]
        self.slot_reserved[slot] = 0
        self.table[slot] = [None] * self.max_pages
        self.slot_req[slot] = None
        self.phase[slot] = "decode"
        self.pos[slot] = self.cursor[slot] = self.remaining[slot] = 0
        self.chain[slot] = 0
        self.evictions += 1

    def step(self):
        self.admit()
        if not any(r is not None for r in self.slot_req):
            return
        for slot in range(self.slots):
            if self.slot_req[slot] is None:
                continue
            tp = self.pos[slot] // self.page_len
            off = self.pos[slot] % self.page_len
            page = self.table[slot][tp]
            if page is None:
                self.table[slot][tp] = self._alloc(slot)
            elif page.ref > 1:  # copy-on-write
                self.table[slot][tp] = self._alloc(slot)
                self._release(page)
        for slot in range(self.slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            rid, prompt, max_new = req
            ctx = prompt[:-1]
            self.pos[slot] += 1
            if self.phase[slot] == "prefill":
                self.cursor[slot] += 1
                cur = self.cursor[slot]
                self.prefill_tokens += 1
                if cur % self.page_len == 0:
                    self._register(slot, ctx, cur)
                if cur == len(ctx):
                    if len(ctx) % self.page_len:
                        self._register(slot, ctx, len(ctx))
                    self.phase[slot] = "handoff"
            else:
                self.done[rid] += 1
                self.decoded_tokens += 1
                self.phase[slot] = "decode"
                self.remaining[slot] -= 1
                if self.remaining[slot] == 0:
                    self.evict(slot)

    def _register(self, slot, ctx, upto):
        tp = (upto - 1) // self.page_len
        key = (self.chain[slot], tuple(ctx[tp * self.page_len:upto]))
        page = self.table[slot][tp]
        if page is None:
            return
        if upto % self.page_len:  # partial tail leaf
            if key in self.cache or self._available() < 1:
                return
            self.reserved += 1
            self.slot_reserved[slot] += 1
            page.ref += 1
            self.cache[key] = (page, None)
            return
        ent = self.cache.get(key)
        if ent is not None:
            self.chain[slot] = ent[1]
            return
        page.ref += 1
        cid = self.next_chain
        self.next_chain += 1
        self.cache[key] = (page, cid)
        self.chain[slot] = cid

    def run(self):
        guard = 0
        while self.queue or any(r is not None for r in self.slot_req):
            self.step()
            guard += 1
            assert guard < 10_000

    # -- observable state -------------------------------------------------

    def snapshot(self):
        pages = {id(p): p.ref for row in self.table for p in row if p}
        for p, _ in self.cache.values():
            pages[id(p)] = p.ref
        return {
            "occupied": [r is not None for r in self.slot_req],
            "refcounts": sorted(pages.values()),
            "free": self.free,
            "cache_entries": len(self.cache),
            "done": dict(self.done),
            "prefill_tokens": self.prefill_tokens,
            "decoded_tokens": self.decoded_tokens,
            "admissions": self.admissions,
            "evictions": self.evictions,
            "prefix_hit_tokens": self.prefix_hit_tokens,
        }


def _real_snapshot(sch):
    live = sch.pages.refcount[2:]
    return {
        "occupied": [r is not None for r in sch._slot_req],
        "refcounts": sorted(int(r) for r in live if r > 0),
        "free": sch.pages.free_pages,
        "cache_entries": sch.pages.prefix_len(),
        "done": {rid: len(toks) for rid, toks in sch.outputs.items()},
        "prefill_tokens": sch.stats.prefill_tokens,
        "decoded_tokens": sch.stats.decoded_tokens,
        "admissions": sch.stats.admissions,
        "evictions": sch.stats.evictions,
        "prefix_hit_tokens": sch.stats.prefix_hit_tokens,
    }


def test_scheduler_trace_oracle(spiking_setup):
    """Randomised submit/step/evict/preempt traces: the real scheduler's
    occupancy, page refcounts, free/cache counts, completion sets and
    token accounting must track the pure-Python oracle exactly, step by
    step, across several seeds (prompt contents are drawn from a small
    pool so prefix hits, partial-tail CoW and page pressure all occur)."""
    cfg, params = spiking_setup
    for trace_seed in (0, 1, 2):
        rng = np.random.RandomState(trace_seed)
        slots, cache_len, page_len = 3, 16, 4
        n_pages = slots * (cache_len // page_len) + 2 - 2 * trace_seed
        sch = BatchScheduler(params, cfg, IntegerBackend(), slots=slots,
                             cache_len=cache_len, paged=True,
                             page_len=page_len, n_pages=n_pages)
        orc = OracleScheduler(slots, cache_len, page_len, n_pages)
        rid = 0
        for op_i in range(40):
            op = rng.choice(["submit", "step", "step", "step", "preempt"])
            if op == "submit":
                base = int(rng.randint(0, 3))  # small pool -> shared prefixes
                length = int(rng.randint(2, 9))
                max_new = int(rng.randint(1, 5))
                prompt = _prompt(base, length)
                sch.submit(prompt, max_new, seed=rid)
                orc.submit(rid, prompt, max_new)
                rid += 1
            elif op == "step":
                sch.step()
                orc.step()
            else:  # preempt the first occupied slot, if any
                occ = [i for i, r in enumerate(sch._slot_req) if r is not None]
                if occ:
                    sch.evict(occ[0], requeue=True)
                    orc.evict(occ[0], requeue=True)
            real, want = _real_snapshot(sch), orc.snapshot()
            assert real == want, (
                f"trace {trace_seed} diverged at op {op_i} ({op}):\n"
                f"real   {real}\noracle {want}")
        sch.run()
        orc.run()
        assert _real_snapshot(sch) == orc.snapshot()
        # every request completed in full
        for r, toks in sch.outputs.items():
            assert orc.done[r] == len(toks)
