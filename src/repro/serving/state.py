"""Serving state: slot-dense DecodeState and the block-paged PagedDecodeState.

Two cache organisations back the continuous batch:

* :class:`DecodeState` — the slot-dense layout: the model cache pytree (ANN
  float KV / recurrent state, or binary spike-train KV for SSA configs)
  keeps one fixed-length region per slot.  Admission and eviction are
  O(slot) scatter updates; freed slots are *zeroed*, which both releases
  the logical region and masks the slot out of the spiking comparators
  (zero AND-counts never spike; ANN caches make stale keys unreachable via
  ``pos = 0``).
* :class:`PagedDecodeState` — the block-paged layout for spiking SSA
  configs: K/V spike trains live in a global physical page pool
  (``models.transformer.paged_pool_schema``) and each slot addresses its
  logical blocks through a row of the page table.  Pages are refcounted
  host-side (:class:`repro.serving.pages.PagePool`) with copy-on-write,
  and a content-keyed prefix cache maps identical prompt prefixes onto the
  *same physical pages* — exact, bit-identical sharing, because prefill
  spike randomness is keyed by (content, position), not by request
  (:func:`content_keys`).  Physical page 0 is the permanently-zero *null
  page* (unallocated blocks read as zero spikes), page 1 the *trash page*
  idle slots write into; both keep every step fixed-shape so the jitted
  decode compiles exactly once.

Cache leaves come in two stackings (see ``models/transformer.py``):
``periods`` leaves are ``[n_periods, slots|n_pages, ...]`` (layer-scanned)
and ``remainder`` leaves drop the leading period axis — the slot/page
helpers below absorb that split so callers never touch it.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import transformer as T
from repro.models.moe import ParallelCtx

Array = jax.Array

# reserved physical pages of every paged pool
NULL_PAGE = 0  # permanently zero; the target of unallocated table entries
TRASH_PAGE = 1  # where idle slots' decode writes land; never read
RESERVED_PAGES = 2


@dataclasses.dataclass
class DecodeState:
    """One continuous batch: model cache + per-slot serving counters.

    cache   — model cache pytree (per-slot ``pos`` counters inside leaves)
    tokens  — [slots] int32, next input token per slot
    seeds   — [slots] uint32, per-request PRN stream id (spiking decode)
    active  — [slots] bool, slot occupancy
    """

    cache: Any
    tokens: Array
    seeds: Array
    active: Array


jax.tree_util.register_pytree_node(
    DecodeState,
    lambda s: ((s.cache, s.tokens, s.seeds, s.active), None),
    lambda _, c: DecodeState(*c),
)


def init_state(cfg, slots: int, cache_len: int) -> DecodeState:
    """A fresh, empty continuous batch of ``slots`` slots."""
    return DecodeState(
        cache=T.init_cache(cfg, slots, cache_len),
        tokens=jnp.zeros((slots,), jnp.int32),
        seeds=jnp.zeros((slots,), jnp.uint32),
        active=jnp.zeros((slots,), bool),
    )


# ---------------------------------------------------------------------------
# Slot-level cache surgery
# ---------------------------------------------------------------------------


def _map_cache(cache, f_periods, f_remainder, *rest):
    out = {}
    if "periods" in cache:
        out["periods"] = jax.tree.map(
            f_periods, cache["periods"], *[r["periods"] for r in rest])
    if "remainder" in cache:
        out["remainder"] = jax.tree.map(
            f_remainder, cache["remainder"], *[r["remainder"] for r in rest])
    return out


def slot_slice(cache, slot) -> Any:
    """A batch-1 view of one slot's cache."""
    return _map_cache(
        cache,
        lambda a: lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
        lambda a: lax.dynamic_slice_in_dim(a, slot, 1, axis=0),
    )


def slot_splice(cache, one, slot) -> Any:
    """Write a batch-1 cache into slot ``slot`` of the batched cache."""
    return _map_cache(
        cache,
        lambda a, o: lax.dynamic_update_slice_in_dim(a, o.astype(a.dtype), slot, axis=1),
        lambda a, o: lax.dynamic_update_slice_in_dim(a, o.astype(a.dtype), slot, axis=0),
        one,
    )


def slot_zero(cache, slot) -> Any:
    """Zero one slot's cache leaves (state release: pos=0, spike trains=0)."""
    return _map_cache(
        cache,
        lambda a: a.at[:, slot].set(jnp.zeros((), a.dtype)),
        lambda a: a.at[slot].set(jnp.zeros((), a.dtype)),
    )


def splice_request(state: DecodeState, slot, cache1, token, seed) -> DecodeState:
    """Admit a prefilled request into ``slot`` (continuous-batching splice)."""
    return DecodeState(
        cache=slot_splice(state.cache, cache1, slot),
        tokens=state.tokens.at[slot].set(token),
        seeds=state.seeds.at[slot].set(seed),
        active=state.active.at[slot].set(True),
    )


def release_slot(state: DecodeState, slot) -> DecodeState:
    """Evict: zero the slot's cache and mark it free."""
    return DecodeState(
        cache=slot_zero(state.cache, slot),
        tokens=state.tokens.at[slot].set(0),
        seeds=state.seeds.at[slot].set(0),
        active=state.active.at[slot].set(False),
    )


# ---------------------------------------------------------------------------
# Content-keyed prefill PRN streams
# ---------------------------------------------------------------------------


def _splitmix32(x: int) -> int:
    """32-bit splitmix finaliser (int -> int in [0, 2^32), well-mixed)."""
    x = (x + 0x9E3779B9) & 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x21F0AAAD) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * 0x735A2D97) & 0xFFFFFFFF
    x ^= x >> 15
    return x


def content_keys(tokens) -> np.ndarray:
    """Per-position *content* PRN stream ids for prompt prefill.

    ``key[i] = H(tokens[0..i])`` — a rolling hash chain, so the spike
    randomness drawn at prompt position ``i`` depends only on the token
    prefix up to ``i`` (plus the position itself, folded in downstream by
    ``_slot_base_keys``), never on the request.  Two requests sharing a
    prompt prefix therefore produce *bit-identical* K/V spike trains for
    the shared positions — the property that lets the paged prefix cache
    map them onto the same physical pages.  Decode keeps per-request
    seeds, so generations still diverge per request.

    (A 32-bit hash collision between different prefixes only makes them
    share comparator randomness, never content — harmless.  The prefix
    cache itself matches on exact token tuples, not on this hash.)
    """
    toks = np.asarray(tokens, np.int64)
    out = np.empty(toks.shape[0], np.uint32)
    h = 0x1C0FFEE5
    for i, t in enumerate(toks):
        h = _splitmix32(h ^ _splitmix32(int(t) & 0xFFFFFFFF))
        out[i] = h
    return out


# ---------------------------------------------------------------------------
# Block-paged serving state (spiking SSA configs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PagedDecodeState:
    """One paged continuous batch: global KV page pool + per-slot counters.

    pool        — paged KV pool pytree (``kp/vp [.., n_pages, T, KV,
                  page_len, hd]`` leaves; no slot axis)
    page_table  — [slots, max_pages] int32 physical page per logical block
                  (NULL_PAGE = unallocated: reads as zero spikes)
    pos         — [slots] int32, each slot's next logical write position
    tokens      — [slots] int32, next input token per slot
    seeds       — [slots] uint32, per-slot PRN stream id
    active      — [slots] bool, slot occupancy
    """

    pool: Any
    page_table: Array
    pos: Array
    tokens: Array
    seeds: Array
    active: Array

    @property
    def page_len(self) -> int:
        leaf = jax.tree.leaves(self.pool)[0]
        return leaf.shape[-2]

    @property
    def n_pages(self) -> int:
        # leaves are [n_pages, ...] (remainder) or [periods, n_pages, ...]
        leaf = jax.tree.leaves(self.pool)[0]
        return leaf.shape[-5]


jax.tree_util.register_pytree_node(
    PagedDecodeState,
    lambda s: ((s.pool, s.page_table, s.pos, s.tokens, s.seeds, s.active), None),
    lambda _, c: PagedDecodeState(*c),
)


def init_paged_state(cfg, slots: int, cache_len: int, page_len: int,
                     n_pages: int) -> PagedDecodeState:
    """A fresh paged batch: all pages free and zeroed, all tables null."""
    assert cache_len % page_len == 0, (
        f"cache_len ({cache_len}) must be a multiple of page_len ({page_len})")
    assert n_pages > RESERVED_PAGES, "pool needs pages beyond null+trash"
    return PagedDecodeState(
        pool=T.init_paged_pool(cfg, n_pages, page_len),
        page_table=jnp.full((slots, cache_len // page_len), NULL_PAGE,
                            jnp.int32),
        pos=jnp.zeros((slots,), jnp.int32),
        tokens=jnp.zeros((slots,), jnp.int32),
        seeds=jnp.zeros((slots,), jnp.uint32),
        active=jnp.zeros((slots,), bool),
    )


def _map_pool(pool, f):
    return jax.tree.map(
        lambda a: f(a) if a.ndim == 5 else jax.vmap(f)(a), pool)


def paged_admit_slot(state: PagedDecodeState, slot, table_row, seed, pos
                     ) -> PagedDecodeState:
    """Open a slot: install its (prefix-hit-prefilled) page-table row and
    starting position (the first *unshared* prompt position — prefix-cache
    hits skip straight past their pages); the scheduler feeds tokens and
    PRN stream ids per step."""
    return dataclasses.replace(
        state,
        page_table=state.page_table.at[slot].set(table_row),
        pos=state.pos.at[slot].set(pos),
        seeds=state.seeds.at[slot].set(seed),
        active=state.active.at[slot].set(True),
    )


def paged_release_slot(state: PagedDecodeState, slot) -> PagedDecodeState:
    """Close a slot: null its table row and zero its counters.  (The host
    :class:`~repro.serving.pages.PagePool` decides which of its pages are
    actually freed — shared pages live on under other refs.)"""
    return dataclasses.replace(
        state,
        page_table=state.page_table.at[slot].set(NULL_PAGE),
        pos=state.pos.at[slot].set(0),
        tokens=state.tokens.at[slot].set(0),
        seeds=state.seeds.at[slot].set(0),
        active=state.active.at[slot].set(False),
    )


def paged_set_table_entry(state: PagedDecodeState, slot, idx, pid
                          ) -> PagedDecodeState:
    """Point one logical block of one slot at a physical page."""
    return dataclasses.replace(
        state, page_table=state.page_table.at[slot, idx].set(pid))


def pool_zero_pages(state: PagedDecodeState, pids: Array) -> PagedDecodeState:
    """Zero a fixed-size batch of physical pages (freed pages must read as
    zero spikes before reuse; pad the batch with TRASH_PAGE ids)."""
    def z(leaf):  # [n_pages, T, KV, page_len, hd]
        # one masked pass that edits the donated pool in place: the TPU
        # lays the page axis out minor-most, so a batched scatter would
        # re-lay the whole pool out and back, and page-by-page updates
        # are strided writes
        keep = jnp.ones((leaf.shape[0],), bool).at[pids].set(False)
        return jnp.where(keep[:, None, None, None, None], leaf,
                         jnp.zeros((), leaf.dtype))

    return dataclasses.replace(state, pool=_map_pool(state.pool, z))


def pool_copy_page(state: PagedDecodeState, src, dst, keep_upto
                   ) -> PagedDecodeState:
    """Copy-on-write: duplicate page ``src`` into ``dst``, keeping only
    in-page positions ``< keep_upto`` (later offsets are zeroed so the new
    owner's unwritten tail stays comparator-masked)."""
    def cp(leaf):  # [n_pages, T, KV, page_len, hd]
        page = leaf[src]
        keep = (jnp.arange(leaf.shape[-2]) < keep_upto)[None, None, :, None]
        return leaf.at[dst].set(jnp.where(keep, page, 0).astype(leaf.dtype))

    return dataclasses.replace(state, pool=_map_pool(state.pool, cp))


def jit_paged_helper(fn, **jit_kwargs):
    """Jit a paged-state helper with the state (argument 0) donated: XLA
    aliases it into the result, so a page-table edit or a page copy updates
    the pool in place instead of writing a fresh copy of it.  The call
    deletes the caller's state — replace it with the result and keep no
    other reference to it."""
    return jax.jit(fn, donate_argnums=0, **jit_kwargs)


paged_admit_slot_jit = jit_paged_helper(paged_admit_slot)
paged_release_slot_jit = jit_paged_helper(paged_release_slot)
paged_set_table_entry_jit = jit_paged_helper(paged_set_table_entry)
pool_zero_pages_jit = jit_paged_helper(pool_zero_pages)
pool_copy_page_jit = jit_paged_helper(pool_copy_page)


# ---------------------------------------------------------------------------
# Jitted step / prefill factories
# ---------------------------------------------------------------------------


def make_paged_decode_fn(cfg, pctx: ParallelCtx, backend,
                         out_shardings=None, plan=None):
    """The single jitted batched step of a *paged* server — decode and
    chunked prefill ride the same compiled function.

    ``(params, state, feed_tok [B], feed_seed [B], feed_mask [B],
    write_pids [B]) -> (logits, state', activity)``.  Slots with
    ``feed_mask`` take their input token and PRN stream id from the feed
    (chunked prefill: the next prompt token keyed by its *content key*;
    admission handoff: the last prompt token keyed by the request seed) —
    everything else rides the state like the dense step (greedy next-token
    written back).  ``write_pids`` names each slot's private physical page
    for this step's K/V write (the scheduler guarantees refcount-1
    ownership via copy-on-write; idle slots point at the trash page).  The
    fed seed persists into ``state.seeds``, so after the admission handoff
    the slot keeps decoding on its request stream with no further feeds.
    Every argument keeps one fixed shape: the step compiles exactly once
    for the server's lifetime (drift/GDC param updates stay
    leaf-value-only, as in :func:`make_decode_fn`).
    """

    def step(params, state: PagedDecodeState, feed_tok, feed_seed, feed_mask,
             write_pids):
        tok = jnp.where(feed_mask, feed_tok, state.tokens)
        seed = jnp.where(feed_mask, feed_seed, state.seeds)
        logits, pool, act = T.paged_decode_step(
            params, state.pool, state.page_table, tok[:, None], state.pos,
            seed, write_pids, cfg, pctx, backend=backend, plan=plan)
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        st = dataclasses.replace(state, pool=pool, pos=state.pos + 1,
                                 tokens=nxt, seeds=seed)
        return logits, st, act

    if out_shardings is None:
        return jax.jit(step)
    return jax.jit(step, out_shardings=out_shardings)


def make_decode_fn(cfg, pctx: ParallelCtx, backend, moe_impl: str,
                   out_shardings=None, plan=None):
    """The single jit-compiled batched decode step over the whole batch.

    ``(params, state) -> (logits [slots,1,V], state', activity [slots])`` —
    every active slot advances one token (greedy next-token written back
    into ``state.tokens``).  Runs entirely through the engine backend's
    spiking primitives for SSA configs; the conventional float path
    otherwise.  ``activity`` is each slot's measured spike-event count this
    step (zeros on the float path) — the scheduler turns it into
    per-request energy.  ``params`` may hold programmed
    ``AIMCDeviceState`` leaves; the drift lifecycle only rewrites leaf
    *values*, so one compile serves the server's whole lifetime.

    ``out_shardings`` (mesh serving — ``repro.distributed``) pins the
    (logits, state, activity) placements so the output state always
    matches the input state's sharding: the compiled step feeds itself
    without resharding or recompiling.
    """

    def step(params, state: DecodeState):
        logits, cache, act = T.decode_step(
            params, state.cache, state.tokens[:, None], cfg, pctx,
            moe_impl=moe_impl, backend=backend, seeds=state.seeds,
            with_activity=True, plan=plan,
        )
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        return logits, dataclasses.replace(state, cache=cache, tokens=nxt), act

    if out_shardings is None:
        return jax.jit(step)
    return jax.jit(step, out_shardings=out_shardings)


def make_prefill_fn(cfg, pctx: ParallelCtx, backend, moe_impl: str,
                    out_shardings=None):
    """Batch-1 prompt prefill through the *same* decode path as serving.

    ``(params, prompt [P], length, seeds [P], cache1) -> (cache1',
    activity)`` — scans the padded prompt through single-token decode,
    gating cache updates on ``idx < length`` so one compiled scan serves
    every prompt in a padding bucket.  Going through ``decode_step`` (not
    the training forward) keeps prefill bit-identical to decoding the
    prompt token by token, which is what makes batched serving exactly
    reproduce single-slot decoding.  ``seeds`` carries one PRN stream id
    per prompt position — the *content keys* of :func:`content_keys`, so
    prefill spike randomness is a pure function of (token prefix,
    position) and identical prompt prefixes produce bit-identical spike
    trains on every serving path, dense or paged.  ``activity`` is the
    prompt's total spike-event count (valid positions only) — prefill
    energy is prompt-length dependent and is booked against the request at
    admission.
    """

    def prefill(params, prompt, length, seeds, cache1):
        def body(carry, xs):
            c, act = carry
            tok, sd, idx = xs
            _, c2, a = T.decode_step(
                params, c, tok[None, None], cfg, pctx, moe_impl=moe_impl,
                backend=backend, seeds=sd[None],
                with_activity=True,
            )
            keep = idx < length
            c = jax.tree.map(lambda a_, b_: jnp.where(keep, b_, a_), c, c2)
            act = act + jnp.where(keep, a[0], 0.0)
            return (c, act), None

        (cache1, act), _ = lax.scan(
            body, (cache1, jnp.zeros((), jnp.float32)),
            (prompt, seeds.astype(jnp.uint32),
             jnp.arange(prompt.shape[0])))
        return cache1, act

    if out_shardings is None:
        return jax.jit(prefill)
    # mesh serving: the batch-1 prefill result is replicated (splice
    # scatters it into the data-sharded batch afterwards)
    return jax.jit(prefill, out_shardings=out_shardings)


splice_request_jit = jax.jit(splice_request)
release_slot_jit = jax.jit(release_slot)
