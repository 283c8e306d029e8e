"""Pallas megakernel: one launch per spiking decoder layer (dense & paged).

The unfused decode path crosses LIF -> spiking-linear -> SSA-decode -> FFN
as separate ``pallas_call``s with HBM round-trips between every primitive.
This module executes the *whole* decoder layer per launch:

* Q/K/V projections, the one-query SSA row, attention-out and the FFN tail
  all run out of VMEM; nothing non-binary reaches HBM;
* every integer count — crossbar currents, the SSA engine's AND-counts
  (§IV-B) — is an MXU product of 0/1 spikes with integer levels.  Each
  operand is exact in bf16 and each sum is exact in the f32 accumulator,
  so the counts equal the integer oracle's under any blocking;
* all heads of a timestep attend at once: 0/1 constant matrices
  (:func:`_head_maps`) move each head's lanes between the ``[H*hd]``
  projection row, an ``[H, KV*hd]`` per-head view and ``[H, hd]`` counts
  on the MXU, so no kernel value is sliced or reshaped along the lane axis
  (which the TPU compiler refuses) and GQA needs no repeated cache.

Layout: the grid runs over slots, one program per slot (dense), or over
(slot, page-table column) with the page axis innermost (paged): each paged
program DMAs exactly one physical page through the scalar-prefetched page
table and accumulates the output AND-counts in a VMEM scratch.  Per-slot
operands are slot-major (``[B, T, ...]``) and caches are ``[.., L, KV*hd]``
so each block spans whole trailing axes.

New-token handling is *additive* instead of scatter-inside-kernel: the
caller passes the **pre-scatter** cache (the row at each slot's ``pos``
is all-zero by the serving invariant), the kernel computes the new K/V
trains itself and adds their score/output contribution
``s_new * v_new`` on top of the cached counts.  Because a zero row
contributes zero AND-counts and ``0 > r`` never fires for the
non-negative comparator draws, this is bit-identical to attending over
the post-scatter cache.  The caller scatters the returned ``k_new`` /
``v_new`` afterwards.  Slots whose write position is masked (dense:
``pos >= L``; paged: the write page not reachable through the slot's
page table — e.g. idle slots parked on the trash page) get their
position comparator forced to an unbeatable value, matching the oracle's
dropped-scatter semantics.

Float-rounding discipline (see ``kernels/ref.py``): the scaled and biased
pre-activations are committed by a store to VMEM, and each membrane runs
through a ``fori_loop`` carry that reads one committed row per step —
bit-identical to :func:`repro.kernels.ref.aimc_spiking_linear_ref` and
hence to the fused-layer oracles :func:`repro.kernels.ref.decode_layer_ref`
/ :func:`repro.kernels.ref.decode_layer_paged_ref`.  ``interpret`` is
resolved by :func:`repro.kernels.platform.interpret_mode`.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops as KOPS
from repro.kernels.platform import interpret_mode

Array = jax.Array

# a comparator draw no AND-count can beat: disables the new-token term
_INVALID_RS = 2 ** 30


def _mm(a: Array, b: Array) -> Array:
    """``a @ b`` for integer-valued operands: bf16 in, f32 out.

    Exact only while every operand has ``|x| <= 256`` (bf16 carries 8
    significant bits) and every partial sum stays below ``2**24``.  Levels
    are clipped to +-127 and spikes are 0/1, but the residual spike stream
    grows by up to 2 per layer, so a model must stay under about 127
    layers for the megakernel to stay bit-exact."""
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _mm_t(a: Array, b: Array) -> Array:
    """``a @ b.T`` for integer-valued operands, exact."""
    return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _head_maps(h: int, kv: int, hd: int):
    """0/1 constants that move heads between lane layouts on the MXU.

    ``hmask [H, H*hd]`` keeps head h's lanes of a query row; ``fold
    [H*hd, KV*hd]`` moves them onto the lanes of head h's KV group;
    ``gmask [H, KV*hd]`` keeps the lanes of that group; ``gsum [KV*hd, hd]``
    sums a group's lanes down to ``hd``; ``spread [hd, H*hd]`` copies a
    head row back out to every head's lanes (``hmask`` then keeps the
    right one)."""
    qi, kj, d = np.arange(h * hd), np.arange(kv * hd), np.arange(hd)
    q_head, k_grp, rep = qi // hd, kj // hd, h // kv
    hmask = np.arange(h)[:, None] == q_head[None, :]
    fold = ((qi[:, None] % hd == kj[None, :] % hd)
            & (q_head[:, None] // rep == k_grp[None, :]))
    gmask = np.arange(h)[:, None] // rep == k_grp[None, :]
    gsum = kj[:, None] % hd == d[None, :]
    spread = d[:, None] == qi[None, :] % hd
    return [jnp.asarray(x, jnp.float32)
            for x in (hmask, fold, gmask, gsum, spread)]


def _next_refs(it, n: int):
    """The next ``n`` refs; each is read where it is used, so a paged
    program reads only what its page step needs."""
    return tuple(next(it) for _ in range(n))


def _query_view(q_row: Array, maps) -> Array:
    """``[1, H*hd]`` query row -> ``[H, KV*hd]``: row h holds head h's
    spikes on the lanes of its KV group, zero elsewhere."""
    return _mm(q_row * maps[0][...], maps[1][...])


def _group_counts(x: Array, maps) -> Array:
    """``[H, KV*hd]`` -> ``[H, hd]``: each head's own group's lanes."""
    return _mm(x * maps[2][...], maps[3][...])


def _heads_to_row(a: Array, maps) -> Array:
    """``[H, hd]`` per-head spikes -> the ``[1, H*hd]`` attention row."""
    return jnp.sum(_mm(a, maps[4][...]) * maps[0][...], axis=0, keepdims=True)


def _spikes(ref_val: Array) -> Array:
    """uint8 spike block -> f32 (the TPU converts unsigned only to ints)."""
    return ref_val.astype(jnp.int32).astype(jnp.float32)


def _lin_lif(x: Array, w, pre_ref, spk_ref, *, beta: float,
             v_thresh: float) -> Array:
    """Quantised crossbar + LIF on ``[T, d_in]`` integer-valued input.

    ``w`` is an ``(int8 levels, f32 scale [1, d_out], f32 bias [1, d_out])``
    triple of refs.  Counts are exact integers; ``* scale`` then
    ``+ bias`` are committed by the store to ``pre_ref``, and the membrane
    reads one committed row per ``fori_loop`` step, writing its spikes to
    ``spk_ref`` — the oracle's exact float structure.  Returns the
    ``[T, d_out]`` spike train."""
    lv, sc, bi = (r[...] for r in w)
    t, dout = x.shape[0], lv.shape[1]
    pre = _mm(x, lv)
    pre = pre * sc
    pre_ref[:, :dout] = pre + bi

    def step(ti, v):
        v = beta * v + pre_ref[pl.ds(ti, 1), :dout]
        spk = (v >= v_thresh).astype(jnp.float32)
        spk_ref[pl.ds(ti, 1), :dout] = spk
        return v * (1.0 - spk)

    lax.fori_loop(0, t, step, jnp.zeros((1, dout), jnp.float32))
    return spk_ref[:, :dout]


def _project(s: Array, wq, wk, wv, pre_ref, spk_ref, kn_ref, vn_ref, **kw):
    """Q/K/V spike trains of one slot; K/V also go out for the scatter."""
    q = _lin_lif(s, wq, pre_ref, spk_ref, **kw)  # [T, H*hd]
    k_new = _lin_lif(s, wk, pre_ref, spk_ref, **kw)  # [T, KV*hd]
    v_new = _lin_lif(s, wv, pre_ref, spk_ref, **kw)
    kn_ref[0] = k_new
    vn_ref[0] = v_new
    return q, k_new, v_new


def _new_token_counts(qv: Array, kn_row: Array, vn_row: Array,
                      rsp: Array, maps) -> Array:
    """``[H, hd]`` output AND-counts the new token adds at one timestep.

    ``qv`` is the ``[H, KV*hd]`` query view, ``kn_row``/``vn_row`` the new
    token's ``[1, KV*hd]`` K/V spikes and ``rsp [H, 1]`` its score draws."""
    cnt = jnp.sum(qv * kn_row, axis=-1, keepdims=True)  # [H, 1]
    s_new = (cnt > rsp.astype(jnp.float32)).astype(jnp.float32)
    return s_new * _group_counts(jnp.broadcast_to(vn_row, qv.shape), maps)


def _tail(s: Array, at: Array, wo, wi, wo2, pre_ref, spk_ref, *,
          with_mlp: bool, **kw) -> Array:
    """Attention-out spiking linear + residual, then the FFN + residual."""
    s1 = s + _lin_lif(at, wo, pre_ref, spk_ref, **kw)
    if with_mlp:
        h1 = _lin_lif(s1, wi, pre_ref, spk_ref, **kw)
        s1 = s1 + _lin_lif(h1, wo2, pre_ref, spk_ref, **kw)
    return s1


def draw_layer_prns(slot_keys: Array, t: int, h: int, l: int, hd: int,
                    h0: Union[int, Array] = 0) -> Tuple[Array, Array]:
    """Per-(slot, global head) comparator draws for one fused layer step.

    Thin reshape over :func:`repro.kernels.ops.draw_slot_decode_prns`
    (same streams as the unfused path: ``r_s ~ U{0..hd-1}`` per cached
    position, ``r_a ~ U{0..L-1}`` per output lane, ``i_max = L``) to the
    fused kernels' ``rs [B,T,H,L]`` / ``ra [B,T,H,hd]`` layouts."""
    rs, ra = KOPS.draw_slot_decode_prns(slot_keys, t, h, l, hd, l, h0)
    b = slot_keys.shape[0]
    return rs.reshape(b, t, h, l), ra.reshape(b, t, h, hd)


def _rs_at_pos(rs4: Array, pos: Array, valid: Array) -> Array:
    """The score-comparator draw each slot's *new* token must beat.

    Gathers ``rs[b, :, :, pos[b]]`` — the draw the oracle's post-scatter
    cache row at ``pos`` sees — and forces it unbeatable where the write
    is masked, reproducing the oracle's dropped-scatter semantics."""
    l = rs4.shape[-1]
    idx = jnp.clip(pos, 0, l - 1).astype(jnp.int32)
    rsp = jnp.take_along_axis(rs4, idx[:, None, None, None], axis=3)[..., 0]
    return jnp.where(valid[:, None, None], rsp, jnp.int32(_INVALID_RS))


def _norm_w(w):
    """(levels, scale, bias|None) -> kernel operands, scale/bias as [1, d_out]."""
    lv, sc, bi = w
    if bi is None:
        bi = jnp.zeros_like(sc, dtype=jnp.float32)
    return (lv, sc.astype(jnp.float32).reshape(1, -1),
            bi.astype(jnp.float32).reshape(1, -1))


def _layer_weights(wq, wk, wv, wo, wi, wo2, with_tail: bool, with_mlp: bool):
    ws = [_norm_w(wq), _norm_w(wk), _norm_w(wv)]
    if with_tail:
        ws.append(_norm_w(wo))
        if with_mlp:
            ws += [_norm_w(wi), _norm_w(wo2)]
    return ws


def _layer_w_refs(it, with_tail: bool, with_mlp: bool):
    wq, wk, wv = _next_refs(it, 3), _next_refs(it, 3), _next_refs(it, 3)
    wo = _next_refs(it, 3) if with_tail else None
    wi = _next_refs(it, 3) if (with_tail and with_mlp) else None
    wo2 = _next_refs(it, 3) if (with_tail and with_mlp) else None
    return wq, wk, wv, wo, wi, wo2


def _scratch(t: int, h: int, hd: int, ws):
    """Attention train [T, H*hd] + the LIF pre-activation / spike rows."""
    wmax = max(w[0].shape[1] for w in ws)
    return [pltpu.VMEM((t, h * hd), jnp.float32),
            pltpu.VMEM((t, wmax), jnp.float32),
            pltpu.VMEM((t, wmax), jnp.float32)]


def _kv_out(x: Array, kv: int, hd: int) -> Array:
    """Kernel's [B, T, KV*hd] f32 new-token train -> [T, B, KV, hd] uint8."""
    b, t, _ = x.shape
    return jnp.moveaxis(x.reshape(b, t, kv, hd), 0, 1).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Dense megakernel: grid over slots, the slot's whole cache in one block
# ---------------------------------------------------------------------------


def _fused_dense_body(*refs, t: int, with_tail: bool, with_mlp: bool,
                      beta: float, v_thresh: float):
    it = iter(refs)
    s_ref = next(it)  # [1, T, d] integer-valued f32
    sk_ref = next(it)  # [1, T, L, KV*hd] u8 pre-scatter key cache
    sv_ref = next(it)  # [1, T, L, KV*hd] u8 pre-scatter value cache
    rs_ref = next(it)  # [1, T, H, L] i32 score-comparator draws
    ra_ref = next(it)  # [1, T, H, hd] i32 output-comparator draws
    rsp_ref = next(it)  # [1, T, H, 1] i32 the new token's score draws
    maps = _next_refs(it, 5)  # _head_maps constants
    wq, wk, wv, wo, wi, wo2 = _layer_w_refs(it, with_tail, with_mlp)
    out_ref = next(it)  # [1, T, ds]
    kn_ref = next(it)  # [1, T, KV*hd] f32
    vn_ref = next(it)
    a_ref = next(it)  # VMEM [T, H*hd] attention train
    pre_ref = next(it)  # VMEM [T, wmax] LIF pre-activations
    spk_ref = next(it)  # VMEM [T, wmax] LIF spikes

    kw = dict(beta=beta, v_thresh=v_thresh)
    s = s_ref[0]  # [T, d]
    q, k_new, v_new = _project(s, wq, wk, wv, pre_ref, spk_ref, kn_ref,
                               vn_ref, **kw)
    for ti in range(t):  # every head of the timestep at once
        qv = _query_view(q[ti:ti + 1], maps)  # [H, KV*hd]
        # score stage: AND-counts of each head's query with its group's keys
        cnt = _mm_t(qv, _spikes(sk_ref[0, ti]))  # [H, L]
        s_spk = (cnt > rs_ref[0, ti].astype(jnp.float32)).astype(jnp.float32)
        # output stage: score spikes' AND-counts with V, plus the new
        # token's term (the cache row at pos is zero pre-scatter)
        cnt_a = _group_counts(_mm(s_spk, _spikes(sv_ref[0, ti])), maps)
        cnt_a = cnt_a + _new_token_counts(qv, k_new[ti:ti + 1],
                                          v_new[ti:ti + 1], rsp_ref[0, ti],
                                          maps)
        a = (cnt_a > ra_ref[0, ti].astype(jnp.float32)).astype(jnp.float32)
        a_ref[ti:ti + 1, :] = _heads_to_row(a, maps)
    at = a_ref[...]  # [T, H*hd]
    if not with_tail:
        out_ref[0] = at
        return
    out_ref[0] = _tail(s, at, wo, wi, wo2, pre_ref, spk_ref,
                       with_mlp=with_mlp, **kw)


@partial(jax.jit, static_argnames=("hd", "with_tail", "with_mlp", "beta",
                                   "v_thresh", "interpret"))
def fused_decode_layer(
    slot_keys: Array,  # [B, 2] uint32 per-slot PRNG keys
    s: Array,  # [T, B, d] integer-valued f32 residual spike stream
    sk: Array,  # [B, T, L, KV, hd] uint8 pre-scatter key cache
    sv: Array,  # [B, T, L, KV, hd] uint8 pre-scatter value cache
    pos: Array,  # [B] int32 write positions (rows >= pos are zero)
    wq, wk, wv,  # (levels int8 [d_in,d_out], scale f32, bias f32|None)
    wo=None, wi=None, wo2=None,
    h0: Union[int, Array] = 0,  # global index of this shard's first head
    *,
    hd: int,
    with_tail: bool = True,
    with_mlp: bool = True,
    beta: float = 0.5,
    v_thresh: float = 1.0,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """One fused spiking decoder layer step over a dense slot cache.

    Returns ``(s_out [T,B,d], k_new [T,B,KV,hd] u8, v_new)`` — the caller
    scatters ``k_new``/``v_new`` into the cache at ``pos`` afterwards.
    ``with_tail=False`` returns the attention train ``a [T,B,H*hd]``
    instead (the tensor-parallel shard building block; ``h0`` names the
    shard's first global head and may be traced).  Bit-exact vs
    :func:`repro.kernels.ref.decode_layer_ref` given the same slot keys.
    """
    t, b, d = s.shape
    l, kv = sk.shape[2], sk.shape[3]
    ws = _layer_weights(wq, wk, wv, wo, wi, wo2, with_tail, with_mlp)
    h = ws[0][0].shape[1] // hd
    rs4, ra4 = draw_layer_prns(slot_keys, t, h, l, hd, h0)
    rsp = _rs_at_pos(rs4, pos, pos < l)
    operands = [jnp.moveaxis(s.astype(jnp.float32), 1, 0),  # [B, T, d]
                sk.astype(jnp.uint8).reshape(b, t, l, kv * hd),
                sv.astype(jnp.uint8).reshape(b, t, l, kv * hd),
                rs4, ra4, rsp[..., None]]
    wflat = _head_maps(h, kv, hd) + [x for w in ws for x in w]
    ds = d if with_tail else h * hd

    def per_slot(shape):
        return pl.BlockSpec((1,) + shape, lambda ib: (ib,) + (0,) * len(shape))

    body = partial(_fused_dense_body, t=t, with_tail=with_tail,
                   with_mlp=with_mlp, beta=beta, v_thresh=v_thresh)
    out_s, kn, vn = pl.pallas_call(
        body,
        grid=(b,),
        in_specs=[per_slot(x.shape[1:]) for x in operands]
        + [pl.BlockSpec(x.shape, lambda ib, n=x.ndim: (0,) * n)
           for x in wflat],
        out_specs=[per_slot((t, ds)), per_slot((t, kv * hd)),
                   per_slot((t, kv * hd))],
        out_shape=(
            jax.ShapeDtypeStruct((b, t, ds), jnp.float32),
            jax.ShapeDtypeStruct((b, t, kv * hd), jnp.float32),
            jax.ShapeDtypeStruct((b, t, kv * hd), jnp.float32),
        ),
        scratch_shapes=_scratch(t, h, hd, ws),
        interpret=interpret_mode(interpret),
    )(*operands, *wflat)
    return jnp.moveaxis(out_s, 0, 1), _kv_out(kn, kv, hd), _kv_out(vn, kv, hd)


# ---------------------------------------------------------------------------
# Paged megakernel: grid (slot, page-table column), page axis innermost
# ---------------------------------------------------------------------------


def _fused_paged_body(*refs, t: int, with_tail: bool, with_mlp: bool,
                      beta: float, v_thresh: float):
    it = iter(refs)
    tbl_ref = next(it)  # scalar-prefetched page table (used by index maps)
    s_ref = next(it)  # [1, T, d]
    kp_ref = next(it)  # [1, T, PL, KV*hd] u8 — one key page
    vp_ref = next(it)  # [1, T, PL, KV*hd] u8 — one value page
    rs_ref = next(it)  # [1, T, 1, H, PL] i32 — this page's draws
    rsp_ref = next(it)  # [1, T, H, 1]
    ra_ref = next(it)  # [1, T, H, hd]
    maps = _next_refs(it, 5)  # _head_maps constants
    wq, wk, wv, wo, wi, wo2 = _layer_w_refs(it, with_tail, with_mlp)
    out_ref = next(it)  # [1, T, ds]
    kn_ref = next(it)  # [1, T, KV*hd] f32
    vn_ref = next(it)
    qv_ref = next(it)  # VMEM [T, H, KV*hd] query views, page-invariant
    acc_ref = next(it)  # VMEM [T, H, hd] output AND-count accumulator
    a_ref = next(it)  # VMEM [T, H*hd] attention train
    pre_ref = next(it)
    spk_ref = next(it)

    del tbl_ref  # consumed by the block index maps, not the body
    kw = dict(beta=beta, v_thresh=v_thresh)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _project_slot():
        # Per-slot projections: LIF is elementwise over the batch, so the
        # one-slot rows are bit-identical to the full-batch oracle's rows.
        q, k_new, v_new = _project(s_ref[0], wq, wk, wv, pre_ref, spk_ref,
                                   kn_ref, vn_ref, **kw)
        for ti in range(t):
            qv = _query_view(q[ti:ti + 1], maps)
            qv_ref[ti] = qv
            # new-token term, additively (see module docstring)
            acc_ref[ti] = _new_token_counts(qv, k_new[ti:ti + 1],
                                            v_new[ti:ti + 1], rsp_ref[0, ti],
                                            maps)

    # every page: AND-counts of the queries against the page's keys vs this
    # page's draws, then the score spikes' AND-counts with its values.
    # Integer sums commute, so page-order accumulation == dense reduction.
    for ti in range(t):
        cnt = _mm_t(qv_ref[ti], _spikes(kp_ref[0, ti]))  # [H, PL]
        s_spk = (cnt > rs_ref[0, ti, 0].astype(jnp.float32)
                 ).astype(jnp.float32)
        acc_ref[ti] += _group_counts(_mm(s_spk, _spikes(vp_ref[0, ti])), maps)

    @pl.when(j == pl.num_programs(1) - 1)
    def _fire():
        for ti in range(t):
            a = (acc_ref[ti] > ra_ref[0, ti].astype(jnp.float32)
                 ).astype(jnp.float32)
            a_ref[ti:ti + 1, :] = _heads_to_row(a, maps)
        at = a_ref[...]
        if not with_tail:
            out_ref[0] = at
            return
        out_ref[0] = _tail(s_ref[0], at, wo, wi, wo2, pre_ref, spk_ref,
                           with_mlp=with_mlp, **kw)


@partial(jax.jit, static_argnames=("hd", "with_tail", "with_mlp", "beta",
                                   "v_thresh", "interpret"))
def fused_decode_layer_paged(
    slot_keys: Array,  # [B, 2] uint32 per-slot PRNG keys
    s: Array,  # [T, B, d] integer-valued f32 residual spike stream
    kpool: Array,  # [P, T, KV, page_len, hd] uint8 pre-scatter key pool
    vpool: Array,  # [P, T, KV, page_len, hd] uint8 pre-scatter value pool
    page_table: Array,  # [B, MP] int32 page ids (0 = null page)
    pos: Array,  # [B] int32 logical write positions
    write_pids: Array,  # [B] int32 physical pages the new K/V scatter into
    wq, wk, wv,  # (levels int8, scale f32, bias f32|None) triples
    wo=None, wi=None, wo2=None,
    h0: Union[int, Array] = 0,
    *,
    hd: int,
    with_tail: bool = True,
    with_mlp: bool = True,
    beta: float = 0.5,
    v_thresh: float = 1.0,
    interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """One fused spiking decoder layer step over a block-paged KV pool.

    The paged twin of :func:`fused_decode_layer`: K/V pages ride the
    scalar-prefetch page-table grid (one physical page DMA'd per program,
    the dense cache never materialised), the output counts accumulate in
    VMEM scratch across pages, and the projections/FFN fire in the first/
    last page program of each slot.  The new token's contribution is
    added only where the write page is actually reachable through the
    slot's table (``table[b, pos // page_len] == write_pids[b]``) — idle
    slots park writes on the unreachable trash page, exactly the unfused
    paged semantics.  Bit-exact vs
    :func:`repro.kernels.ref.decode_layer_paged_ref`.
    """
    t, b, d = s.shape
    kv, pl_ = kpool.shape[2], kpool.shape[3]
    mp = page_table.shape[1]
    l = mp * pl_
    ws = _layer_weights(wq, wk, wv, wo, wi, wo2, with_tail, with_mlp)
    h = ws[0][0].shape[1] // hd
    rs4, ra4 = draw_layer_prns(slot_keys, t, h, l, hd, h0)
    reach = jnp.take_along_axis(
        page_table, jnp.clip(pos // pl_, 0, mp - 1)[:, None], axis=1)[:, 0]
    rsp = _rs_at_pos(rs4, pos, (pos < l) & (reach == write_pids))
    def pool(x):  # [P, T, KV, PL, hd] -> [P, T, PL, KV*hd]
        x = jnp.swapaxes(x.astype(jnp.uint8), 2, 3)
        return x.reshape(x.shape[:3] + (kv * hd,))

    rs5 = jnp.swapaxes(rs4.reshape(b, t, h, mp, pl_), 2, 3)  # [B,T,MP,H,PL]
    operands = [jnp.moveaxis(s.astype(jnp.float32), 1, 0),  # [B, T, d]
                pool(kpool), pool(vpool), rs5, rsp[..., None], ra4]
    wflat = _head_maps(h, kv, hd) + [x for w in ws for x in w]
    ds = d if with_tail else h * hd

    def per_slot(shape):
        return pl.BlockSpec((1,) + shape,
                            lambda ib, j, tbl: (ib,) + (0,) * len(shape))

    def page(ib, j, tbl):
        return (tbl[ib, j], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, mp),
        in_specs=[
            per_slot((t, d)),
            pl.BlockSpec((1, t, pl_, kv * hd), page),
            pl.BlockSpec((1, t, pl_, kv * hd), page),
            pl.BlockSpec((1, t, 1, h, pl_),
                         lambda ib, j, tbl: (ib, 0, j, 0, 0)),
            per_slot((t, h, 1)),
            per_slot((t, h, hd)),
        ] + [pl.BlockSpec(x.shape, lambda ib, j, tbl, n=x.ndim: (0,) * n)
             for x in wflat],
        out_specs=[per_slot((t, ds)), per_slot((t, kv * hd)),
                   per_slot((t, kv * hd))],
        scratch_shapes=[pltpu.VMEM((t, h, kv * hd), jnp.float32),
                        pltpu.VMEM((t, h, hd), jnp.float32)]
        + _scratch(t, h, hd, ws),
    )
    body = partial(_fused_paged_body, t=t, with_tail=with_tail,
                   with_mlp=with_mlp, beta=beta, v_thresh=v_thresh)
    out_s, kn, vn = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((b, t, ds), jnp.float32),
            jax.ShapeDtypeStruct((b, t, kv * hd), jnp.float32),
            jax.ShapeDtypeStruct((b, t, kv * hd), jnp.float32),
        ),
        interpret=interpret_mode(interpret),
    )(page_table.astype(jnp.int32), *operands, *wflat)
    return jnp.moveaxis(out_s, 0, 1), _kv_out(kn, kv, hd), _kv_out(vn, kv, hd)
