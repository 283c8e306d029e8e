"""BatchScheduler: continuous-batching request scheduler over DecodeState.

A miniature vLLM-style serving loop for the Xpikeformer engine:

* **admission** — pending requests splice into free slots *mid-flight*
  (prefilled batch-1 through the same decode path, then scattered into the
  batch), so the running slots never wait for the batch to drain.  Per-slot
  PRN stream ids + per-slot position counters make admission bit-exact for
  already-running slots: a request's token stream is a pure function of
  (params, prompt, seed), never of batch composition.
* **eviction** — finished (or explicitly evicted) slots release their state:
  cache leaves are zeroed, which both frees the logical page and masks the
  slot out of the spiking comparators.
* **decode** — one jit-compiled batched ``decode_step`` advances every slot;
  the scheduler only does O(slots) host bookkeeping per step.
* **paged serving** (``paged=True``, spiking SSA configs) — K/V spike
  trains live in a global block-paged pool
  (:class:`~repro.serving.state.PagedDecodeState` +
  :class:`~repro.serving.pages.PagePool`): refcounted pages with
  copy-on-write, an exact-prefix cache that maps identical prompt prefixes
  onto the *same physical pages* (bit-identical spike trains — prefill PRN
  streams are content-keyed, see
  :func:`~repro.serving.state.content_keys`), **chunked prefill** (prompt
  tokens ride the same batched decode step as everyone else's decode, one
  position per step per prefilling slot, instead of a batch-1
  prefill-then-splice), and admission that blocks on *free pages* rather
  than free slots.  Generated token streams are bit-identical to dense
  serving on the bit-exact backends.
* **drift lifecycle** — when the params hold programmed PCM state
  (:class:`repro.aimc_device.AIMCDeviceState`) and a
  :class:`~repro.aimc_device.DriftPolicy` is set, the scheduler advances
  the device clock from the decode-step wall clock (or a fixed per-step
  quantum) and runs periodic GDC recalibration.  Both are pure leaf-value
  pytree updates, so the jitted ``decode_step`` is **never recompiled** by
  aging or recalibration.
* **energy metering** — every decode step returns per-slot measured
  spike-event counts; the scheduler converts them to joules (event count x
  per-event op energy + static per-token cost, Table-II constants) and
  accounts them per request (:attr:`BatchScheduler.request_energy_j`) and
  in :class:`ServeStats`.

The decode math runs through the engine's pluggable :class:`~repro.engine.
Backend` for spiking SSA configs (reference / integer / pallas serve
identically — the integer oracle is the correctness contract) and the
conventional float path otherwise.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import aimc_device as AD
from repro.energy import model as EM
from repro.kernels.plan import build_decode_plan
from repro.models import transformer as T
from repro.models.moe import ParallelCtx
from repro.obs import Telemetry
from repro.obs import trace as TR
from repro.serving import state as ST
from repro.serving.pages import PagePool

Array = jax.Array

# paged-slot lifecycle: consuming prompt positions -> feeding the last
# prompt token on the request's own PRN stream -> riding greedy argmax
PREFILL, HANDOFF, DECODE = "prefill", "handoff", "decode"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Array  # [P] int32
    max_new: int
    seed: int
    # host-side views, filled by submit(): the prompt as numpy, its context
    # length (prompt minus the last token, which seeds decode), and the
    # per-position content keys that make prefill spike randomness a pure
    # function of (token prefix, position) — the prefix-sharing contract
    prompt_np: Optional[np.ndarray] = None
    ckeys: Optional[np.ndarray] = None

    @property
    def n_ctx(self) -> int:
        return len(self.prompt_np) - 1


@dataclasses.dataclass
class ServeStats:
    requests: int = 0
    data_shards: int = 1  # mesh data-axis size (1 = single device)
    model_shards: int = 1  # mesh model-axis size
    decode_steps: int = 0
    decoded_tokens: int = 0
    prefill_tokens: int = 0
    admissions: int = 0
    evictions: int = 0
    wall_s: float = 0.0  # whole serve loop (admission/prefill included)
    decode_s: float = 0.0  # batched decode_step calls only
    spike_events: float = 0.0  # measured residual-stream spike events
    energy_j: float = 0.0  # metered inference energy (events x op energies)
    t_device_s: float = 0.0  # PCM device clock at the last decode step
    recalibrations: int = 0  # GDC recalibrations run by the drift policy
    # paged serving (zeros on the dense path)
    prefix_hits: int = 0  # prefix-cache page hits across admissions
    prefix_hit_tokens: int = 0  # prompt positions skipped via shared pages
    cow_copies: int = 0  # copy-on-write page duplications
    pages_in_use_peak: int = 0  # peak simultaneously-referenced pool pages
    peak_active_slots: int = 0  # max slots concurrently occupied

    @property
    def tokens_per_sec(self) -> float:
        """End-to-end decoded-token throughput (prefill time included)."""
        return self.decoded_tokens / max(self.wall_s, 1e-9)

    @property
    def j_per_token(self) -> float:
        """Metered joules per decoded token.

        Same guarded-denominator convention as :attr:`tokens_per_sec` /
        :attr:`decode_tokens_per_sec` (``max(x, 1e-9)``).  Zero-token
        behaviour: with nothing decoded *and* nothing metered this is
        ``0.0``; with booked energy but zero decoded tokens (a
        prefill-only or all-preempted run) it is astronomically large —
        deliberately, since the true cost per delivered token of such a
        run is unbounded, and the old ``max(decoded_tokens, 1)`` floor
        silently under-reported it as ``energy_j``."""
        return self.energy_j / max(self.decoded_tokens, 1e-9)

    @property
    def decode_tokens_per_sec(self) -> float:
        """Decode-phase throughput: tokens per second spent inside the
        batched ``decode_step`` — the batching win, independent of how
        prompts were prefilled (the batch-1 prefill scan is the same work
        in any slot configuration)."""
        return self.decoded_tokens / max(self.decode_s, 1e-9)


def _bucket(n: int, minimum: int = 8) -> int:
    """Pad prompt lengths to power-of-two buckets (one prefill compile each)."""
    b = minimum
    while b < n:
        b *= 2
    return b


class BatchScheduler:
    """Continuous-batching scheduler: submit prompts, run, collect outputs.

    Greedy decoding; a request finishes after ``max_new`` tokens.  Outputs
    are collected in :attr:`outputs` (rid -> list of generated token ids).
    """

    def __init__(
        self,
        params: Any,
        cfg,
        backend=None,
        *,
        slots: int = 4,
        cache_len: int = 64,
        pctx: Optional[ParallelCtx] = None,
        moe_impl: Optional[str] = None,
        drift: Optional[AD.DriftPolicy] = None,
        placement=None,
        paged: bool = False,
        page_len: int = 8,
        n_pages: Optional[int] = None,
        decode_kernel: str = "auto",
        obs: Optional[Telemetry] = None,
    ):
        self.placement = placement  # repro.distributed.Executor | None
        if placement is not None:
            params = placement.place_params(params)
        self.params = params
        self.cfg = cfg
        self.backend = backend
        self.slots = slots
        self.cache_len = cache_len
        self.pctx = pctx or ParallelCtx()
        self.moe_impl = moe_impl or ("ep_a2a" if cfg.is_moe else "dense")
        self.drift = drift
        self.paged = bool(paged)
        # one DecodePlan per scheduler lifetime: the jitted decode step
        # closes over it, so kernel selection can never recompile mid-serve
        if T._spiking_decode_enabled(cfg):
            self.plan = build_decode_plan(
                cfg, backend, layout="paged" if self.paged else "dense",
                kernel=decode_kernel, page_len=page_len)
        else:
            if decode_kernel == "fused":
                raise ValueError(
                    "decode kernel 'fused' needs a spiking SSA config, "
                    f"not {cfg.name!r}")
            self.plan = None
        if self.paged:
            if not T.paged_decode_supported(cfg):
                raise ValueError(
                    "paged serving needs a spiking SSA stack of pure "
                    f"attention blocks, not {cfg.name!r}")
            if cache_len % page_len:
                raise ValueError(
                    f"cache_len ({cache_len}) must be a multiple of "
                    f"page_len ({page_len})")
            self.page_len = page_len
            self.max_pages = cache_len // page_len
            # default pool: the same cache memory a dense server of this
            # slot count would allocate (prefix sharing turns that budget
            # into extra concurrency)
            self.n_pages = (slots * self.max_pages + ST.RESERVED_PAGES
                            if n_pages is None else n_pages)
            self.pages = PagePool(self.n_pages, page_len)
            self.state = self._place_state(ST.init_paged_state(
                cfg, slots, cache_len, page_len, self.n_pages))
            if placement is None:
                decode_out = None
                self._admit_slot = ST.paged_admit_slot_jit
                self._release_slot = ST.paged_release_slot_jit
                self._set_entry = ST.paged_set_table_entry_jit
                self._zero_pages_fn = ST.pool_zero_pages_jit
                self._copy_page = ST.pool_copy_page_jit
            else:
                decode_out = placement.paged_decode_out_shardings(
                    slots, cache_len, self.n_pages, page_len)
                state_sh = placement.paged_state_shardings(
                    slots, cache_len, self.n_pages, page_len)
                self._admit_slot = jax.jit(ST.paged_admit_slot,
                                           out_shardings=state_sh)
                self._release_slot = jax.jit(ST.paged_release_slot,
                                             out_shardings=state_sh)
                self._set_entry = jax.jit(ST.paged_set_table_entry,
                                          out_shardings=state_sh)
                self._zero_pages_fn = jax.jit(ST.pool_zero_pages,
                                              out_shardings=state_sh)
                self._copy_page = jax.jit(ST.pool_copy_page,
                                          out_shardings=state_sh)
            self._decode = ST.make_paged_decode_fn(
                cfg, self.pctx, backend, out_shardings=decode_out,
                plan=self.plan)
            self._prefill = None
            # host mirrors: page-table rows, per-slot logical positions,
            # prefill cursors, slot phases, outstanding page reservations
            self._table_rows = np.full((slots, self.max_pages), ST.NULL_PAGE,
                                       np.int32)
            self._slot_pos = [0] * slots
            self._cursor = [0] * slots
            self._phase = [DECODE] * slots
            self._slot_reserved = [0] * slots
            self._chain = [0] * slots  # prefix-cache chain id per slot
        else:
            self.state = self._place_state(ST.init_state(cfg, slots, cache_len))
            if placement is None:
                decode_out = prefill_out = None
                prefill_backend = backend
                self._splice = ST.splice_request_jit
                self._release = ST.release_slot_jit
            else:
                # mesh serving: slots ride the data axis, spiking kernels are
                # tensor-parallel over model; out-shardings are pinned so the
                # compiled decode feeds itself without resharding/recompiling
                decode_out = placement.decode_out_shardings(slots, cache_len)
                prefill_out = placement.replicated
                prefill_backend = placement.prefill_backend
                state_sh = placement.state_shardings(slots, cache_len)
                self._splice = jax.jit(ST.splice_request, out_shardings=state_sh)
                self._release = jax.jit(ST.release_slot, out_shardings=state_sh)
            self._decode = ST.make_decode_fn(cfg, self.pctx, backend,
                                             self.moe_impl,
                                             out_shardings=decode_out,
                                             plan=self.plan)
            self._prefill = ST.make_prefill_fn(cfg, self.pctx, prefill_backend,
                                               self.moe_impl,
                                               out_shardings=prefill_out)
        self._queue: Deque[Request] = deque()
        self._slot_req: List[Optional[Request]] = [None] * slots
        self._remaining: List[int] = [0] * slots
        self.outputs: Dict[int, List[int]] = {}
        # per-request measured energy / spike events (rid -> totals)
        self.request_energy_j: Dict[int, float] = {}
        self.request_spikes: Dict[int, float] = {}
        self.stats = self._fresh_stats()
        self._next_rid = 0
        # PCM device clock (drift lifecycle): picks up wherever the
        # programmed params already are — the device does not rejuvenate
        self._t_device = AD.device_time(params)
        self._last_recal = self._t_device
        self._t_image = self._t_device  # device time of the last image fold
        self._programmed = AD.has_device_state(params)
        self.stats.t_device_s = self._t_device
        # static per-decoded-token energy for spiking SSA configs (the
        # activity-independent ADC/periphery/LIF/comparator work)
        if getattr(cfg, "spiking", False) and cfg.attention_kind == "ssa":
            self._e_token_pj = EM.lm_decode_token_energy_pj(
                cfg.d_model, cfg.num_heads, cfg.resolved_head_dim, cfg.d_ff,
                cfg.num_layers, cfg.spike_T, cache_len, cfg.vocab_size)
        else:
            self._e_token_pj = 0.0
        self._e_event_pj = EM.decode_synapse_energy_pj()
        # telemetry: host-side only (see repro.obs) — binding it can never
        # touch the jitted step or change a token/joule
        self.obs: Optional[Telemetry] = None
        self._stat_marks: Dict[str, float] = {}
        self._pool_marks: Dict[str, float] = {}
        if obs is not None:
            self.attach_obs(obs)

    # -- telemetry -------------------------------------------------------

    # ServeStats fields mirrored into monotone counters (single source of
    # truth stays ServeStats; the registry syncs by delta once per step)
    _STAT_COUNTERS = (
        ("decode_steps", "decode_steps_total",
         "batched decode_step invocations"),
        ("decoded_tokens", "decoded_tokens_total", "greedy tokens decoded"),
        ("prefill_tokens", "prefill_tokens_total",
         "prompt positions prefilled (chunked or batch-1)"),
        ("admissions", "admissions_total", "requests admitted into slots"),
        ("evictions", "evictions_total", "slot evictions (finish or preempt)"),
        ("spike_events", "spike_events_total",
         "measured residual-stream spike events"),
        ("energy_j", "energy_joules_total",
         "metered inference energy (spike events x op energies)"),
        ("recalibrations", "gdc_recalibrations_total",
         "GDC recalibrations run by the drift policy"),
        ("prefix_hits", "prefix_page_hits_total",
         "prefix-cache page hits across admissions"),
        ("prefix_hit_tokens", "prefix_hit_tokens_total",
         "prompt positions skipped via shared pages"),
        ("cow_copies", "cow_copies_total", "copy-on-write page duplications"),
    )

    def attach_obs(self, obs: Telemetry) -> None:
        """Install (or replace) the telemetry bundle: resolve metric
        handles once and arm the page-pool guard dump sites."""
        self.obs = obs
        m = obs.metrics
        self._h_step = m.histogram(
            "decode_step_seconds", "batched decode_step latency")
        self._g_active = m.gauge("active_slots", "slots holding a request")
        self._g_queue = m.gauge(
            "scheduler_queue_depth", "submitted-not-yet-admitted requests")
        self._g_clock = m.gauge(
            "device_clock_seconds", "PCM device clock (drift lifecycle)")
        self._g_gain = m.gauge(
            "gdc_gain_mean", "mean GDC gain across programmed crossbars "
            "(set at bind and after each recalibration)")
        self._g_pages_in_use = m.gauge(
            "pool_pages_in_use", "physical KV pages referenced")
        self._g_pages_free = m.gauge(
            "pool_pages_free", "physical KV pages on the free list")
        self._c_lookups = m.counter(
            "prefix_lookups_total", "prefix-cache block lookups",
            ("result",))
        self._stat_counters = {
            field: m.counter(name, help) for field, name, help in
            self._STAT_COUNTERS}
        self._stat_marks = {f: 0.0 for f, _, _ in self._STAT_COUNTERS}
        self._pool_marks = {"hit": 0.0, "miss": 0.0}
        self._g_clock.set(self._t_device)
        if self._programmed:
            self._g_gain.set(AD.gdc_gain_summary(self.params))
        self._sync_stat_counters()
        self._arm_pool_guard()

    def detach_obs(self) -> None:
        """Remove the telemetry bundle (the exact inverse of
        :meth:`attach_obs`): metric handles are dropped, the page-pool
        guard hook is disarmed, and subsequent runs book nothing.  The
        registry itself is untouched — counters keep their lifetime
        values.  A later re-attach rebases the delta marks at zero and
        mirrors the scheduler's *current* ServeStats as fresh deltas,
        so call :meth:`reset` between detach and a re-attach to the
        same registry to avoid double-booking the interlude."""
        self.obs = None
        self._stat_counters = {}
        self._stat_marks = {}
        if self.paged:
            self.pages.on_violation = None

    def _arm_pool_guard(self) -> None:
        if self.obs is not None and self.paged:
            self.pages.on_violation = self._on_guard

    def _on_guard(self, reason: str) -> None:
        """Invariant-guard dump site (PagePool double-free/use-after-free,
        evict-unoccupied): postmortem first, the raise proceeds after."""
        if self.obs is not None:
            self.obs.guard_dump(reason)

    def _sync_stat_counters(self) -> None:
        """Mirror ServeStats into the registry's counters by delta."""
        st = self.stats
        marks = self._stat_marks
        for field, counter in self._stat_counters.items():
            cur = float(getattr(st, field))
            delta = cur - marks[field]
            if delta > 0:
                counter.inc(delta)
                marks[field] = cur

    def _obs_step(self, step_s: float, decoded: int) -> None:
        """Per-decode-step telemetry: latency histogram, occupancy gauges,
        counter sync, pool stats, profiler window."""
        obs = self.obs
        if obs is None:
            return
        self._h_step.observe(step_s)
        self._g_active.set(sum(r is not None for r in self._slot_req))
        self._g_queue.set(len(self._queue))
        if self.paged:
            pool = self.pages
            self._g_pages_in_use.set(pool.in_use)
            self._g_pages_free.set(pool.free_pages)
            for result, cur in (("hit", pool.prefix_hits),
                                ("miss", pool.prefix_misses)):
                delta = cur - self._pool_marks[result]
                if delta > 0:
                    self._c_lookups.inc(delta, result)
                    self._pool_marks[result] = cur
        self._sync_stat_counters()
        if obs.profiler is not None:
            obs.profiler.tick()

    def _fresh_stats(self) -> ServeStats:
        if self.placement is None:
            return ServeStats()
        return ServeStats(data_shards=self.placement.data,
                          model_shards=self.placement.model)

    def _place_state(self, state):
        return state if self.placement is None else self.placement.place_state(state)

    def _place_params(self, params):
        return params if self.placement is None else self.placement.place_params(params)

    def set_params(self, params: Any) -> None:
        """Swap the served params (e.g. a newly-programmed tree) and re-read
        the device lifecycle bookkeeping from them."""
        self.params = self._place_params(params)
        self._programmed = AD.has_device_state(params)
        self._t_device = AD.device_time(params)
        self._last_recal = self._t_device
        self._t_image = self._t_device
        self.stats.t_device_s = self._t_device

    def reset(self) -> None:
        """Drop all requests and state but keep the compiled step functions
        (fresh server, warm jit cache — used by benchmarks and tests).
        The PCM device clock is *not* reset: drift is physical."""
        if self.paged:
            self.pages = PagePool(self.n_pages, self.page_len)
            self.state = self._place_state(ST.init_paged_state(
                self.cfg, self.slots, self.cache_len, self.page_len,
                self.n_pages))
            self._table_rows[:] = ST.NULL_PAGE
            self._slot_pos = [0] * self.slots
            self._cursor = [0] * self.slots
            self._phase = [DECODE] * self.slots
            self._slot_reserved = [0] * self.slots
            self._chain = [0] * self.slots
        else:
            self.state = self._place_state(
                ST.init_state(self.cfg, self.slots, self.cache_len))
        self._queue.clear()
        self._slot_req = [None] * self.slots
        self._remaining = [0] * self.slots
        self.outputs = {}
        self.request_energy_j = {}
        self.request_spikes = {}
        self.stats = self._fresh_stats()
        self.stats.t_device_s = self._t_device
        if self.obs is not None:
            # counters are lifetime-monotone; only the delta marks rebase
            # onto the fresh ServeStats / PagePool
            self._stat_marks = {f: 0.0 for f, _, _ in self._STAT_COUNTERS}
            self._pool_marks = {"hit": 0.0, "miss": 0.0}
            self._arm_pool_guard()

    # -- request intake ------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new: int, seed: Optional[int] = None) -> int:
        """Queue a request; returns its rid.  ``seed`` fixes the request's
        spike PRN stream (defaults to the rid) — the same (prompt, seed)
        decodes identically no matter how it is batched."""
        prompt = jnp.asarray(prompt, jnp.int32)
        assert prompt.ndim == 1 and prompt.shape[0] >= 1, "prompt must be [P>=1]"
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if prompt.shape[0] + max_new > self.cache_len:
            raise ValueError(
                f"prompt ({prompt.shape[0]}) + max_new ({max_new}) exceeds "
                f"cache_len ({self.cache_len})"
            )
        if self.paged:
            worst = -(-(int(prompt.shape[0]) - 1 + max_new) // self.page_len)
            usable = self.n_pages - ST.RESERVED_PAGES
            if worst > usable:
                raise ValueError(
                    f"request needs up to {worst} pages but the pool only "
                    f"has {usable} usable — it could never be admitted")
        rid = self._next_rid
        self._next_rid += 1
        pnp = np.asarray(prompt, np.int32)
        # content keys for the prompt context (prefill PRN streams): pure
        # functions of the token prefix, so identical prefixes prefill
        # bit-identically on every serving path — dense or paged
        req = Request(rid, prompt, max_new, rid if seed is None else seed,
                      prompt_np=pnp, ckeys=ST.content_keys(pnp[:-1]))
        self._queue.append(req)
        self.stats.requests += 1
        if self.obs is not None:
            self.obs.trace(TR.SUBMIT, rid=rid, prompt_len=int(pnp.shape[0]),
                           max_new=max_new, seed=req.seed)
        return rid

    # -- slot management -----------------------------------------------

    def admit(self) -> int:
        """Admit queued requests into free slots (continuous batching).

        Dense mode prefills each admitted prompt through a batch-1 scan of
        the same decode path, then scatters the filled cache into the slot
        while the other slots' state is untouched.  Paged mode reserves
        pages, resolves prefix-cache hits, and leaves the remaining prompt
        positions to chunked prefill inside the batched step — admission
        blocks on *free pages*, not just free slots.  Returns #admitted."""
        if self.paged:
            return self._admit_paged()
        admitted = 0
        for slot in range(self.slots):
            if not self._queue or self._slot_req[slot] is not None:
                continue
            req = self._queue.popleft()
            p = req.prompt
            n_ctx = int(p.shape[0]) - 1  # last prompt token feeds the first decode
            padded = _bucket(max(n_ctx, 1))
            prompt_pad = jnp.zeros((padded,), jnp.int32).at[:n_ctx].set(p[:-1])
            ckeys_pad = np.zeros((padded,), np.uint32)
            ckeys_pad[:n_ctx] = req.ckeys
            cache1 = T.init_cache(self.cfg, 1, self.cache_len)
            cache1, pre_act = self._prefill(
                self.params, prompt_pad, jnp.int32(n_ctx),
                jnp.asarray(ckeys_pad), cache1,
            )
            self.state = self._splice(
                self.state, slot, cache1, p[-1], jnp.uint32(req.seed))
            self._slot_req[slot] = req
            self._remaining[slot] = req.max_new
            self.outputs[req.rid] = []
            # prefill energy is prompt-length dependent: book the measured
            # prompt spike events + static per-token cost at admission
            spikes = float(pre_act)
            e_j = (spikes * self._e_event_pj + n_ctx * self._e_token_pj) * 1e-12
            self.request_spikes[req.rid] = (
                self.request_spikes.get(req.rid, 0.0) + spikes)
            self.request_energy_j[req.rid] = (
                self.request_energy_j.get(req.rid, 0.0) + e_j)
            self.stats.spike_events += spikes
            self.stats.energy_j += e_j
            self.stats.prefill_tokens += n_ctx
            self.stats.admissions += 1
            admitted += 1
            if self.obs is not None:
                self.obs.trace(TR.ADMIT, rid=req.rid, slot=slot,
                               prefill_tokens=n_ctx, mode="dense")
        self.stats.peak_active_slots = max(
            self.stats.peak_active_slots,
            sum(r is not None for r in self._slot_req))
        return admitted

    # -- paged-mode page plumbing --------------------------------------

    def _zero_freed(self, pids: List[int]) -> None:
        """Zero freed physical pages on device (fixed-size jitted batches,
        padded with the trash page so the step compiles once)."""
        chunk = self.max_pages
        for i in range(0, len(pids), chunk):
            batch = np.full((chunk,), ST.TRASH_PAGE, np.int32)
            part = pids[i:i + chunk]
            batch[:len(part)] = part
            self.state = self._zero_pages_fn(self.state, jnp.asarray(batch))

    def _cow(self, slot: int, tp: int, src: int, keep_upto: int) -> int:
        """Copy-on-write: give ``slot`` exclusive ownership of logical block
        ``tp`` by copying the shared page's valid prefix (``< keep_upto``
        in-page positions) into a fresh page and repointing its table."""
        dst = self.pages.alloc(reserved=True)
        self._slot_reserved[slot] -= 1
        self.state = self._copy_page(self.state, jnp.int32(src),
                                     jnp.int32(dst), jnp.int32(keep_upto))
        self.state = self._set_entry(self.state, jnp.int32(slot),
                                     jnp.int32(tp), jnp.int32(dst))
        self._table_rows[slot, tp] = dst
        if self.pages.release(src):  # cache entry may have been LRU-evicted
            self._zero_freed([src])
        self.pages.cow_copies += 1
        return dst

    def _register_prefix(self, slot: int, upto: int) -> None:
        """Publish the page holding context positions up to ``upto`` (the
        end of a just-completed block, or the whole context for a partial
        tail block) in the prefix cache, keyed by (parent chain id, the
        block's own tokens) — O(page_len) hashing per block, exact by
        chain construction.  Tail blocks cost one reserved page later —
        the registrant's next write copy-on-writes — so they are
        registered opportunistically, only when the pool has slack."""
        req = self._slot_req[slot]
        tp = (upto - 1) // self.page_len
        key = (self._chain[slot],
               tuple(req.prompt_np[tp * self.page_len:upto].tolist()))
        pid = int(self._table_rows[slot, tp])
        if pid == ST.NULL_PAGE:
            return
        if upto % self.page_len:  # partial tail block: a chain leaf
            if self.pages.prefix_contains(key) or self.pages.available() < 1:
                return
            self.pages.reserve(1)
            self._slot_reserved[slot] += 1
            self.pages.prefix_register(key, pid, chain=False)
            return
        # full block: adopt the (new or already-canonical) chain id so the
        # slot's next block links to it
        self._chain[slot] = self.pages.prefix_register(key, pid, chain=True)

    def _admit_paged(self) -> int:
        """Paged admission: exact-prefix page hits + worst-case page
        reservation.  FIFO order is preserved — a request that cannot
        reserve its pages blocks the queue (head-of-line) rather than
        being overtaken, so admission order never depends on prompt sizes."""
        admitted = 0
        for slot in range(self.slots):
            if not self._queue or self._slot_req[slot] is not None:
                continue
            req = self._queue[0]
            ctx = req.prompt_np[:-1]
            n_ctx = req.n_ctx
            pl_ = self.page_len
            total_pages = -(-(n_ctx + req.max_new) // pl_)
            # leading-chain prefix match: full blocks while the chain is
            # unbroken (each link keyed by (parent chain id, block
            # tokens)), then — only off the complete full-block chain —
            # the partial tail leaf
            hits: List[int] = []
            chain = 0  # the empty-prefix root
            k = pl_
            while k <= n_ctx:
                ent = self.pages.prefix_lookup(
                    (chain, tuple(ctx[k - pl_:k].tolist())))
                if ent is None:
                    break
                hits.append(ent[0])
                chain = ent[1]
                k += pl_
            partial_pid = None
            if len(hits) == n_ctx // pl_ and n_ctx % pl_:
                ent = self.pages.prefix_lookup(
                    (chain, tuple(ctx[len(hits) * pl_:].tolist())))
                partial_pid = None if ent is None else ent[0]
            # worst-case unshared pages (a partial hit still allocates its
            # page at the copy-on-write); block on pool pressure
            needed = total_pages - len(hits)
            if self.pages.available() < needed:
                freed = self.pages.prefix_evict(
                    needed - self.pages.available())
                if freed:
                    self._zero_freed(freed)
            if self.pages.available() < needed:
                # hand the hit refs back; prefix_evict may already have
                # dropped these pages' cache entries, in which case ours
                # was the last ref and the page must be zeroed before reuse
                freed = [pid for pid in hits if self.pages.release(pid)]
                if partial_pid is not None and self.pages.release(partial_pid):
                    freed.append(partial_pid)
                if freed:
                    self._zero_freed(freed)
                break
            self._queue.popleft()
            self.pages.reserve(needed)
            row = np.full((self.max_pages,), ST.NULL_PAGE, np.int32)
            row[:len(hits)] = hits
            cursor = len(hits) * pl_
            if partial_pid is not None:
                row[n_ctx // pl_] = partial_pid
                cursor = n_ctx
            self._table_rows[slot] = row
            self.state = self._admit_slot(
                self.state, jnp.int32(slot), jnp.asarray(row),
                jnp.uint32(req.seed), jnp.int32(cursor))
            self._slot_req[slot] = req
            self._remaining[slot] = req.max_new
            self._slot_pos[slot] = cursor
            self._cursor[slot] = cursor
            self._phase[slot] = PREFILL if cursor < n_ctx else HANDOFF
            self._slot_reserved[slot] = needed
            self._chain[slot] = chain  # registrations link after the hits
            self.outputs[req.rid] = []
            self.stats.prefix_hit_tokens += cursor
            self.stats.prefix_hits += len(hits) + (partial_pid is not None)
            self.stats.admissions += 1
            admitted += 1
            if self.obs is not None:
                self.obs.trace(TR.ADMIT, rid=req.rid, slot=slot, mode="paged",
                               prefix_hit_tokens=cursor,
                               reserved_pages=needed)
        self.stats.peak_active_slots = max(
            self.stats.peak_active_slots,
            sum(r is not None for r in self._slot_req))
        return admitted

    def free_slots(self) -> int:
        """Slots not currently holding a request (queued submissions are
        *not* counted — they only claim a slot at the next ``step()``'s
        admission; see :meth:`queued_requests`)."""
        return sum(r is None for r in self._slot_req)

    def queued_requests(self) -> List[Request]:
        """Submitted-but-not-yet-admitted requests, FIFO order (the front
        door reads this to budget slots/pages it has already committed)."""
        return list(self._queue)

    def slot_of(self, rid: int) -> Optional[int]:
        """The slot currently decoding request ``rid`` (None when the
        request is queued, finished, or unknown)."""
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.rid == rid:
                return slot
        return None

    def preempt(self, rid: int) -> Request:
        """Yank request ``rid`` out of the server — release its slot (or
        drop it from the queue) and forget its collected output — and
        return the :class:`Request` so the caller controls re-admission.

        Unlike ``evict(slot, requeue=True)`` (which re-queues internally
        and re-admits at the very next step), preemption hands scheduling
        *back to the caller*: the front door re-submits the same (prompt,
        max_new, seed) when the tenant's energy bucket refills, and token
        purity makes the restarted decode bit-identical — already-streamed
        tokens replay exactly.  Energy already booked to ``rid`` stays
        booked (preemption does not refund the joules it wasted)."""
        slot = self.slot_of(rid)
        if slot is not None:
            req = self._slot_req[slot]
            if self.obs is not None:
                self.obs.trace(TR.PREEMPT, rid=rid, slot=slot,
                               streamed=len(self.outputs.get(rid, ())))
            self.evict(slot)
            self.outputs.pop(rid, None)
            return req
        for req in self._queue:
            if req.rid == rid:
                self._queue.remove(req)
                self.outputs.pop(rid, None)
                return req
        raise ValueError(f"preempt of unknown/finished request rid={rid}")

    def evict(self, slot: int, requeue: bool = False) -> None:
        """Release a slot's state (zero or refcount-release cache pages,
        clear occupancy).

        With ``requeue=True`` the in-flight request restarts from its
        prompt on a later admission (preemption); otherwise its collected
        output is kept as-is.  Evicting an unoccupied slot raises — the
        use-after-evict / double-free guard."""
        req = self._slot_req[slot]
        if req is None:
            self._on_guard(f"evict of unoccupied slot {slot}")
            raise ValueError(f"evict of unoccupied slot {slot} "
                             "(double-evict or use-after-evict)")
        if self.obs is not None:
            self.obs.trace(TR.EVICT, rid=req.rid, slot=slot, requeue=requeue)
        if requeue:
            self._queue.appendleft(req)
            self.outputs.pop(req.rid, None)
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        if self.paged:
            freed = []
            for pid in self._table_rows[slot]:
                if pid != ST.NULL_PAGE and self.pages.release(int(pid)):
                    freed.append(int(pid))
            if freed:
                self._zero_freed(freed)
            self.pages.unreserve(self._slot_reserved[slot])
            self._slot_reserved[slot] = 0
            self._table_rows[slot] = ST.NULL_PAGE
            self._slot_pos[slot] = 0
            self._cursor[slot] = 0
            self._phase[slot] = DECODE
            self._chain[slot] = 0
            self.state = self._release_slot(self.state, jnp.int32(slot))
        else:
            self.state = self._release(self.state, slot)
        self.stats.evictions += 1

    # -- serving loop --------------------------------------------------

    def _book_position(self, rid: int, spikes: float) -> None:
        """Book one served position's energy — measured spike events x the
        per-event op energy plus the static per-token cost — against the
        request and the aggregate stats.  One formula for dense decode,
        paged decode and paged chunked-prefill positions, so the
        paged==dense energy equality holds by construction."""
        e_j = (spikes * self._e_event_pj + self._e_token_pj) * 1e-12
        self.request_spikes[rid] = self.request_spikes.get(rid, 0.0) + spikes
        self.request_energy_j[rid] = (
            self.request_energy_j.get(rid, 0.0) + e_j)
        self.stats.spike_events += spikes
        self.stats.energy_j += e_j

    def step(self) -> int:
        """Admit, then advance every active slot one token.  Returns the
        number of tokens decoded (0 when idle).

        Each step also (a) meters energy — the decode returns per-slot
        measured spike-event counts, converted to joules and booked against
        the slot's request — and (b) advances the PCM drift lifecycle when
        a :class:`~repro.aimc_device.DriftPolicy` is set on programmed
        params (device clock from decode wall time, periodic GDC
        recalibration), without recompiling the jitted decode."""
        if self.paged:
            return self._step_paged()
        self.admit()
        if not any(r is not None for r in self._slot_req):
            return 0
        t0 = time.perf_counter()  # monotonic: durations must survive NTP
        logits, self.state, act = self._decode(*self._decode_args())
        nxt = np.asarray(self.state.tokens)  # syncs the step
        step_s = time.perf_counter() - t0
        self.stats.decode_s += step_s
        self.stats.decode_steps += 1
        act = np.asarray(act)
        obs = self.obs
        decoded = 0
        for slot in range(self.slots):
            req = self._slot_req[slot]
            if req is None:
                continue
            out = self.outputs[req.rid]
            out.append(int(nxt[slot]))
            decoded += 1
            if obs is not None:
                obs.trace(TR.FIRST_TOKEN if len(out) == 1 else TR.DECODE,
                          rid=req.rid, slot=slot, token=int(nxt[slot]),
                          pos=len(out))
            self._book_position(req.rid, float(act[slot]))
            self._remaining[slot] -= 1
            if self._remaining[slot] == 0:
                if obs is not None:
                    obs.trace(TR.FINISH, rid=req.rid, slot=slot,
                              tokens=len(out))
                self.evict(slot)
        self.stats.decoded_tokens += decoded
        self._advance_device_clock(step_s)
        self._obs_step(step_s, decoded)
        return decoded

    def _step_paged(self) -> int:
        """One paged batched step: chunked prefill and decode interleaved.

        Each occupied slot advances one position — a *prompt* position
        (chunked prefill: the next context token fed on its content-keyed
        PRN stream), the admission handoff (the last prompt token on the
        request's own stream), or a decode position (greedy argmax riding
        the state).  Before the step, every writing slot is guaranteed an
        exclusive physical page for its target block (allocation at block
        boundaries, copy-on-write off shared pages); idle slots write the
        trash page.  One jitted function serves all of it, compiled once.
        Returns #tokens decoded (prompt chunks don't count)."""
        self.admit()
        if not any(r is not None for r in self._slot_req):
            return 0
        b = self.slots
        feed = self._blank_feed()
        feed_tok, feed_seed, feed_mask, write_pids = feed
        for slot in range(b):
            req = self._slot_req[slot]
            if req is None:
                feed_mask[slot] = True  # pin idle slots to token 0 / stream 0
                continue
            p = self._slot_pos[slot]
            tp, off = divmod(p, self.page_len)
            pid = int(self._table_rows[slot, tp])
            if pid == ST.NULL_PAGE:  # block boundary: open a fresh page
                pid = self.pages.alloc(reserved=True)
                self._slot_reserved[slot] -= 1
                self._table_rows[slot, tp] = pid
                self.state = self._set_entry(self.state, jnp.int32(slot),
                                             jnp.int32(tp), jnp.int32(pid))
            elif self.pages.refcount[pid] > 1:  # shared (prefix cache): CoW
                pid = self._cow(slot, tp, pid, off)
            write_pids[slot] = pid
            phase = self._phase[slot]
            if phase == PREFILL:
                cur = self._cursor[slot]
                feed_tok[slot] = req.prompt_np[cur]
                feed_seed[slot] = req.ckeys[cur]
                feed_mask[slot] = True
            elif phase == HANDOFF:
                feed_tok[slot] = req.prompt_np[-1]
                feed_seed[slot] = req.seed
                feed_mask[slot] = True
        t0 = time.perf_counter()  # monotonic: durations must survive NTP
        logits, self.state, act = self._decode(*self._decode_args(feed))
        nxt = np.asarray(self.state.tokens)  # syncs the step
        step_s = time.perf_counter() - t0
        self.stats.decode_s += step_s
        self.stats.decode_steps += 1
        act = np.asarray(act)
        obs = self.obs
        decoded = 0
        for slot in range(b):
            req = self._slot_req[slot]
            if req is None:
                continue
            self._slot_pos[slot] += 1
            phase = self._phase[slot]
            if phase == PREFILL:
                self._cursor[slot] += 1
                cur = self._cursor[slot]
                self.stats.prefill_tokens += 1
                if obs is not None:
                    obs.trace(TR.PREFILL_CHUNK, rid=req.rid, slot=slot,
                              pos=cur, n_ctx=req.n_ctx)
                if cur % self.page_len == 0:  # completed block: publish it
                    self._register_prefix(slot, cur)
                if cur == req.n_ctx:
                    if req.n_ctx % self.page_len:
                        self._register_prefix(slot, req.n_ctx)
                    self._phase[slot] = HANDOFF
            else:
                out = self.outputs[req.rid]
                out.append(int(nxt[slot]))
                decoded += 1
                if obs is not None:
                    obs.trace(TR.FIRST_TOKEN if len(out) == 1 else TR.DECODE,
                              rid=req.rid, slot=slot, token=int(nxt[slot]),
                              pos=len(out))
                if phase == HANDOFF:
                    self._phase[slot] = DECODE
                self._remaining[slot] -= 1
            self._book_position(req.rid, float(act[slot]))
            if self._remaining[slot] == 0:
                if obs is not None:
                    obs.trace(TR.FINISH, rid=req.rid, slot=slot,
                              tokens=len(self.outputs[req.rid]))
                self.evict(slot)
        self.stats.decoded_tokens += decoded
        self.stats.pages_in_use_peak = max(self.stats.pages_in_use_peak,
                                           self.pages.peak_in_use)
        self.stats.cow_copies = self.pages.cow_copies
        self._advance_device_clock(step_s)
        self._obs_step(step_s, decoded)
        return decoded

    def _advance_device_clock(self, step_wall_s: float) -> None:
        """Drift lifecycle: age the programmed PCM state and periodically
        GDC-recalibrate, per the :class:`~repro.aimc_device.DriftPolicy`.

        Leaf-value-only pytree updates (``drift_tree_jit`` /
        ``recalibrate_tree_jit``): shapes, dtypes and the params treedef are
        unchanged, so the compiled ``decode_step`` stays warm.

        The scalar clock advances every step, but the O(params) image fold
        (drift re-quantisation) only runs when the drift factor has moved
        by at least ~half an int8 image LSB since the last fold — at device
        ages of hours a per-token refresh could not change a single image
        level and would just burn host time — and always right before a
        GDC recalibration (which reads the state's own clock)."""
        pol = self.drift
        if pol is None or not self._programmed:
            return
        dt = pol.seconds_per_step if pol.seconds_per_step > 0 else (
            step_wall_s * pol.time_scale)
        self._t_device += dt
        due_recal = (pol.recal_interval_s > 0
                     and self._t_device - self._last_recal >= pol.recal_interval_s)
        # half-LSB criterion: (t/t_image)^-nu_mean moved by > 0.5/127
        ratio = (1.0 - 0.5 / 127.0) ** (-1.0 / max(pol.cfg.drift_nu_mean, 1e-3))
        due_image = self._t_device >= max(self._t_image,
                                          pol.cfg.drift_t0_s) * ratio
        if due_recal or due_image:
            self.params = self._place_params(AD.drift_tree_jit(
                self.params, jnp.float32(self._t_device), pol.cfg))
            self._t_image = self._t_device
        if due_recal:
            self.params = self._place_params(
                AD.recalibrate_tree_jit(self.params, pol.cfg))
            self._last_recal = self._t_device
            self.stats.recalibrations += 1
            if self.obs is not None:
                # one host read per recal event (rare): the post-recal gain
                # is *the* signal that GDC actually repaired the drift
                gain = AD.gdc_gain_summary(self.params)
                self._g_gain.set(gain)
                self.obs.trace(TR.GDC_RECAL, t_device_s=self._t_device,
                               gain=gain, n=self.stats.recalibrations)
        self.stats.t_device_s = self._t_device
        if self.obs is not None:
            self._g_clock.set(self._t_device)

    def _blank_feed(self):
        """The paged step's per-slot feed (token, PRN seed, feed mask,
        write page), zeroed with every slot writing the trash page;
        :meth:`_step_paged` fills in the occupied slots."""
        b = self.slots
        return (np.zeros((b,), np.int32), np.zeros((b,), np.uint32),
                np.zeros((b,), bool), np.full((b,), ST.TRASH_PAGE, np.int32))

    def _decode_args(self, feed=None):
        """Arguments of the jitted step: params, state and, when paged,
        the per-slot feed (a blank one if none is given)."""
        if not self.paged:
            return self.params, self.state
        feed = self._blank_feed() if feed is None else feed
        return (self.params, self.state) + tuple(jnp.asarray(x) for x in feed)

    def lower_decode_step(self):
        """The jitted batched decode step, lowered at the current state's
        shapes: ``.as_text()`` shows the program each step runs (e.g. its
        Mosaic kernels), without executing it."""
        return self._decode.lower(*self._decode_args())

    def run(self) -> Dict[int, List[int]]:
        """Serve until the queue and all slots drain; returns outputs."""
        t0 = time.perf_counter()  # monotonic: wall_s is a duration
        while self._queue or any(r is not None for r in self._slot_req):
            self.step()
        self.stats.wall_s += time.perf_counter() - t0
        if self.obs is not None and self.obs.profiler is not None:
            self.obs.profiler.stop()  # close a capture wider than the run
        return self.outputs
