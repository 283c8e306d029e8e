"""Pallas TPU kernel: stochastic spiking attention (SSA), bit-packed.

TPU adaptation of the paper's N x N array of stochastic attention cells
(§IV-B): the ASIC streams 1-bit Q/K/V through AND gates + counters; on TPU
we pack 32 timestep-lanes... no — we pack the *contraction axis* into
uint32 lanes so one VPU ``and`` + ``population_count`` replaces 32 AND
gates + counter increments:

  stage 1 (scores):   contraction over d_k  -> Q,K packed along d_k
  stage 2 (output):   contraction over n'   -> S packed in-kernel along n',
                                               V packed along n'

The Bernoulli comparators use *externally supplied* uniform random integers
(r_s in [0,d_k), r_a in [0,N)) — mirroring the SSA engine's shared LFSR
array feeding the tiles (§IV-B-3), and making the kernel bit-exact
reproducible against the pure-jnp oracle in ``ref.py``.

Grid: one program per (t, b*h) pair — the hardware pipelines timesteps
through the same stateless tile, we parallelise them.  Block shapes keep
the whole [N, N] score tile in VMEM (N <= 128 per the paper's edge-AI
sizing; ops.py tiles larger N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _popcount(x: Array) -> Array:
    # int32 counts: the TPU lowering has no reductions over unsigned ints
    return lax.population_count(x).astype(jnp.int32)


def _pack_bits_kernel_axis(s: Array) -> Array:
    """Pack binary int32 [M, n] -> uint32 [M, n//32] inside the kernel.

    The word ``w`` of row ``i`` is ``sum_j s[i, 32w + j] << j``.  A
    reshape that splits the lane axis into (words, 32) is refused by the
    TPU compiler, so the sum is two MXU products with constant
    power-of-two matrices, one per 16-bit half: every operand (0/1 spikes,
    powers of two up to 2^15) is exact in bf16 and every sum (< 2^16) is
    exact in the f32 accumulator, so the packing is exact on any backend.
    """
    n = s.shape[-1]
    w = n // 32
    j = lax.broadcasted_iota(jnp.int32, (n, w), 0)
    in_word = (j >> 5) == lax.broadcasted_iota(jnp.int32, (n, w), 1)
    bit = j & 31
    lo_w = jnp.where(in_word & (bit < 16), 1 << bit, 0).astype(jnp.float32)
    hi_w = jnp.where(in_word & (bit >= 16), 1 << (bit - 16), 0
                     ).astype(jnp.float32)
    sf = s.astype(jnp.float32)
    lo = jnp.dot(sf, lo_w, preferred_element_type=jnp.float32)
    hi = jnp.dot(sf, hi_w, preferred_element_type=jnp.float32)
    packed = (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)
    return lax.bitcast_convert_type(packed, jnp.uint32)


def _ssa_kernel(qp_ref, kp_ref, vp_ref, rs_ref, ra_ref, out_ref, *, n: int, d: int, causal: bool):
    """One (t, b*h) tile.

    qp [N, Wd] u32   — Q packed along d_k
    kp [N, Wd] u32   — K packed along d_k
    vp [Wn, D] u32   — V packed along n'
    rs [N, N] i32    — LFSR integers for the score comparators
    ra [N, D] i32    — LFSR integers for the output comparators
    out [N, D] u8    — binary attention output A^t
    """
    qp = qp_ref[0]
    kp = kp_ref[0]
    # stage 1: counts[i,j] = popcount_d(q_i & k_j)   (AND + counter, §IV-B-2)
    anded = qp[:, None, :] & kp[None, :, :]  # [N, N, Wd]
    counts_s = jnp.sum(_popcount(anded), axis=-1).astype(jnp.int32)
    if causal:
        ii = lax.broadcasted_iota(jnp.int32, (n, n), 0)
        jj = lax.broadcasted_iota(jnp.int32, (n, n), 1)
        counts_s = jnp.where(jj <= ii, counts_s, 0)
    s = (counts_s > rs_ref[0]).astype(jnp.int32)  # Bernoulli comparator

    # stage 2: pack S along n', AND with packed V, popcount over n'
    sp = _pack_bits_kernel_axis(s)  # [N, Wn]
    vp = vp_ref[0]  # [Wn, D]
    anded2 = sp[:, :, None] & vp[None, :, :]  # [N, Wn, D]
    counts_a = jnp.sum(_popcount(anded2), axis=1).astype(jnp.int32)
    out_ref[0] = (counts_a > ra_ref[0]).astype(jnp.uint8)


def _ssa_decode_body(qp_ref, kp_ref, vp_ref, rs_ref, ra_ref, out_ref):
    """One (b, t, h) decode cell: a single stochastic attention row.

    qp [1, Wd] u32   — the new token's query spikes, packed along d_k
    kp [L, Wd] u32   — cached key train, packed along d_k
    vp [Wl, D] u32   — cached value train, packed along the cache axis
    rs [1, L] i32    — LFSR integers for the score comparators
    ra [1, D] i32    — LFSR integers for the output comparators
    out [1, D] u8    — the token's binary attention output

    Invalid (not-yet-written / freed) cache rows are all-zero, so their
    AND-counts are 0 and never beat a comparator draw — validity masking
    is implicit, which is what lets one fixed-shape kernel serve every
    slot of a continuous batch regardless of per-slot position.
    """
    qp = qp_ref[0]  # [1, Wd]
    kp = kp_ref[0]  # [L, Wd]
    # stage 1: counts[j] = popcount_d(q & k_j)
    anded = qp & kp  # [L, Wd] (q broadcast over cache rows)
    counts_s = jnp.sum(_popcount(anded), axis=-1).astype(jnp.int32)[None, :]
    s = (counts_s > rs_ref[0]).astype(jnp.int32)  # [1, L]
    # stage 2: pack S along the cache axis, AND with packed V, popcount
    sp = _pack_bits_kernel_axis(s)  # [1, Wl]
    anded2 = jnp.swapaxes(sp, 0, 1) & vp_ref[0]  # [Wl, D]
    counts_a = jnp.sum(_popcount(anded2), axis=0).astype(jnp.int32)[None, :]
    out_ref[0] = (counts_a > ra_ref[0]).astype(jnp.uint8)


def ssa_decode_kernel(
    qp: Array,  # [G, 1, Wd] u32  (G = B*T*H fused grid axis)
    kp: Array,  # [G, L, Wd] u32
    vp: Array,  # [G, Wl, D] u32
    rs: Array,  # [G, 1, L] i32
    ra: Array,  # [G, 1, D] i32
    *,
    interpret: bool,
) -> Array:
    g, l, wd = kp.shape
    wl = vp.shape[1]
    d = vp.shape[2]
    return pl.pallas_call(
        _ssa_decode_body,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, 1, wd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, l, wd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, wl, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, l), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 1, d), jnp.uint8),
        interpret=interpret,
    )(qp, kp, vp, rs, ra)


def _ssa_decode_paged_body(tbl_ref, qp_ref, kp_ref, vp_ref, rs_ref, ra_ref,
                           out_ref, acc_ref):
    """One (slot, timestep, head, page) paged decode cell.

    The stochastic attention row of :func:`_ssa_decode_body`, decomposed
    over the slot's KV pages: the grid's last axis walks the slot's page
    table (the K/V block specs gather page ``tbl[b, j]`` straight from the
    physical pool via scalar-prefetch index maps — the dense cache is never
    materialised), and the output AND-counts accumulate in ``acc_ref``
    across pages.  This is exact: the score comparator is elementwise per
    cached position (no cross-position normalisation in SSA), and the
    output counts are integer sums, so any page-order accumulation
    reproduces the dense reduction bit-for-bit.

    qp [1, Wd] u32    — the new token's query spikes, packed along d_k
    kp [PLp, Wd] u32  — ONE key page (gathered through the page table)
    vp [Wp, D] u32    — one value page, packed along the in-page position
    rs [1, PLp] i32   — this page's slice of the score-comparator integers
    ra [1, D] i32     — output-comparator integers (page-invariant)
    acc [1, D] i32    — output AND-count accumulator (VMEM scratch)
    out [1, D] u8     — binary attention output, written at the last page
    """
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qp = qp_ref[0, 0, 0]  # [1, Wd]
    kp = kp_ref[0, 0, 0]  # [PLp, Wd]
    counts_s = jnp.sum(_popcount(qp & kp), axis=-1).astype(jnp.int32)[None, :]
    s = (counts_s > rs_ref[0, 0, 0, 0]).astype(jnp.int32)  # [1, PLp]
    sp = _pack_bits_kernel_axis(s)  # [1, Wp]
    anded = jnp.swapaxes(sp, 0, 1) & vp_ref[0, 0, 0]  # [Wp, D]
    acc_ref[...] += jnp.sum(_popcount(anded), axis=0).astype(jnp.int32)[None, :]

    @pl.when(j == pl.num_programs(3) - 1)
    def _fire():
        out_ref[0, 0, 0] = (acc_ref[...] > ra_ref[0, 0, 0]).astype(jnp.uint8)


def ssa_decode_paged_kernel(
    page_table: Array,  # [B, MP] i32 page ids (scalar-prefetched)
    qp: Array,  # [B, T, H, 1, Wd] u32
    kp: Array,  # [P, T, KV, PLp, Wd] u32 — the physical key page pool
    vp: Array,  # [P, T, KV, Wp, D] u32 — value pool, packed along position
    rs: Array,  # [B, T, H, MP, 1, PLp] i32
    ra: Array,  # [B, T, H, 1, D] i32
    *,
    interpret: bool,
) -> Array:
    """Paged SSA decode: grid (slot, timestep, head, page-table column).

    The page table rides scalar prefetch so the K/V block index maps can
    dereference it — each program DMAs exactly one physical page out of the
    pool, never a dense per-slot cache.  GQA is folded into the index maps
    (query head ``ih`` reads KV head ``ih // (H // KV)``)."""
    b, t, h, _, wd = qp.shape
    mp = page_table.shape[1]
    plp = kp.shape[3]
    wp, d = vp.shape[3], vp.shape[4]
    rep = h // kp.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, t, h, mp),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, wd), lambda ib, it, ih, j, tbl: (ib, it, ih, 0, 0)),
            pl.BlockSpec((1, 1, 1, plp, wd),
                         lambda ib, it, ih, j, tbl: (tbl[ib, j], it, ih // rep, 0, 0)),
            pl.BlockSpec((1, 1, 1, wp, d),
                         lambda ib, it, ih, j, tbl: (tbl[ib, j], it, ih // rep, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, 1, plp),
                         lambda ib, it, ih, j, tbl: (ib, it, ih, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, d), lambda ib, it, ih, j, tbl: (ib, it, ih, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, 1, d),
                               lambda ib, it, ih, j, tbl: (ib, it, ih, 0, 0)),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.int32)],
    )
    return pl.pallas_call(
        _ssa_decode_paged_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h, 1, d), jnp.uint8),
        interpret=interpret,
    )(page_table, qp, kp, vp, rs, ra)


def ssa_attention_kernel(
    qp: Array,  # [G, N, Wd] u32  (G = T*B*H fused grid axis)
    kp: Array,  # [G, N, Wd] u32
    vp: Array,  # [G, Wn, D] u32
    rs: Array,  # [G, N, N] i32
    ra: Array,  # [G, N, D] i32
    *,
    n: int,
    d: int,
    causal: bool,
    interpret: bool,
) -> Array:
    g, _, wd = qp.shape
    wn = vp.shape[1]
    kern = functools.partial(_ssa_kernel, n=n, d=d, causal=causal)
    return pl.pallas_call(
        kern,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, n, wd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, wd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, wn, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n, d), jnp.uint8),
        interpret=interpret,
    )(qp, kp, vp, rs, ra)
