"""Jit'd public wrappers for the Pallas kernels: packing, padding, PRNs.

These are what the framework calls; each wrapper
* packs binary spike operands into uint32 lanes (32 AND-gates per VPU op),
* pads shapes to kernel block multiples,
* draws the comparator integers from a counter-based PRNG (the software
  stand-in for the SSA engine's shared 32-bit LFSR array — all four bytes
  of each word are used, per §IV-B-3 / core.spikes.split_prn_bytes),
* and resolve ``interpret=None`` through :func:`repro.kernels.platform.
  interpret_mode` (interpreted on CPU, compiled on TPU).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels.aimc_matmul import (aimc_matmul_counts_kernel,
                                       aimc_spiking_linear_kernel,
                                       drift_requantize_kernel)
from repro.kernels.lif import lif_kernel
from repro.kernels.platform import interpret_mode
from repro.kernels.ssa_attention import (ssa_attention_kernel,
                                         ssa_decode_kernel,
                                         ssa_decode_paged_kernel)

Array = jax.Array


def _rup(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pack_bits(x: Array, axis: int = -1) -> Array:
    """Pack a binary array into uint32 along ``axis`` (size % 32 == 0)."""
    x = jnp.moveaxis(x, axis, -1)
    *lead, n = x.shape
    w = n // 32
    xr = x.reshape(*lead, w, 32).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    packed = jnp.sum(xr * weights, axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(packed, -1, axis)


def unpack_bits(x: Array, n: int, axis: int = -1) -> Array:
    xm = jnp.moveaxis(x, axis, -1)
    bits = (xm[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    out = bits.reshape(*xm.shape[:-1], xm.shape[-1] * 32)[..., :n]
    return jnp.moveaxis(out.astype(jnp.uint8), -1, axis)


def draw_comparator_prns(key: Array, shape_s: Tuple[int, ...], shape_a: Tuple[int, ...],
                         d: int, n: int) -> Tuple[Array, Array]:
    """Uniform integers for the two Bernoulli comparator banks.

    r_s ~ U{0..d-1}, r_a ~ U{0..n-1}; with d and n powers of two these are
    exactly the low bits of an LFSR word (§IV-B-2)."""
    k1, k2 = jax.random.split(key)
    rs = jax.random.randint(k1, shape_s, 0, d, dtype=jnp.int32)
    ra = jax.random.randint(k2, shape_a, 0, n, dtype=jnp.int32)
    return rs, ra


@partial(jax.jit, static_argnames=("causal", "interpret"))
def ssa_attention_packed(
    q: Array,  # [T, B, H, N, D] binary (any int/float in {0,1})
    k: Array,
    v: Array,
    key: Array,
    *,
    causal: bool = False,
    interpret: Optional[bool] = None,
) -> Array:
    """Bit-packed SSA attention; returns uint8 spikes [T,B,H,N,D].

    N and D may be arbitrary: the wrapper zero-pads both pack axes up to
    multiples of 32 and slices the result back.  The comparator PRNs are
    drawn at the *logical* (unpadded) shapes with the *logical* ranges
    (r_s ~ U{0..d-1}, r_a ~ U{0..n-1}) so the output is bit-identical to
    the unpadded integer oracle given the same key — padded q/k rows and
    v columns are all-zero, so their AND-counts are 0 and can never beat
    a non-negative comparator draw."""
    t, b, h, n, d = q.shape
    g = t * b * h
    # comparator integers at logical shapes/ranges (bit-exactness contract)
    rs, ra = draw_comparator_prns(key, (g, n, n), (g, n, d), d, n)
    n_pad = (-n) % 32
    d_pad = (-d) % 32
    np_, dp_ = n + n_pad, d + d_pad
    qf = q.reshape(g, n, d).astype(jnp.uint8)
    kf = k.reshape(g, n, d).astype(jnp.uint8)
    vf = v.reshape(g, n, d).astype(jnp.uint8)
    if n_pad or d_pad:
        pad = ((0, 0), (0, n_pad), (0, d_pad))
        qf = jnp.pad(qf, pad)
        kf = jnp.pad(kf, pad)
        vf = jnp.pad(vf, pad)
        rs = jnp.pad(rs, ((0, 0), (0, n_pad), (0, n_pad)))
        ra = jnp.pad(ra, ((0, 0), (0, n_pad), (0, d_pad)))
    qp = pack_bits(qf, axis=-1)  # [G, N, D/32]
    kp = pack_bits(kf, axis=-1)
    vp = pack_bits(vf, axis=-2)  # pack over n': [G, N/32, D]
    out = ssa_attention_kernel(
        qp, kp, vp, rs, ra, n=np_, d=dp_, causal=causal,
        interpret=interpret_mode(interpret),
    )
    return out[:, :n, :d].reshape(t, b, h, n, d)


def draw_slot_decode_prns(
    slot_keys: Array,  # [B, 2] uint32 — per-slot PRNG keys
    t: int, h: int, l: int, d: int, i_max: int,
    h0: Union[int, Array] = 0,
) -> Tuple[Array, Array]:
    """Per-(slot, head) comparator integers for one SSA decode step.

    Each serving slot draws from its *own* key so the stream a request sees
    depends only on (request seed, position) — never on which other
    requests share the batch.  That is the bit-exactness contract of
    continuous batching: admitting a request mid-flight cannot perturb the
    spikes of already-running slots.

    Within a slot, every attention head draws from ``fold_in(slot_key,
    global_head_index)`` — the stream is ``f(seed, pos, head)``.  Per-head
    keying is what makes *tensor-parallel* decode bit-exact: a shard that
    owns heads ``[h0, h0+h)`` of a mesh-sharded SSA engine passes its
    global head offset ``h0`` (possibly traced, e.g. derived from
    ``lax.axis_index``) and draws exactly the integers the single-device
    oracle draws for those heads (see ``repro.distributed``).

    Returns ``(rs [B,T*H,1,L], ra [B,T*H,1,D])`` — t-major over the T*H
    axis, matching the (b, t, h) grid order of the packed decode wrapper —
    with r_s ~ U{0..d-1}, r_a ~ U{0..i_max-1}.
    """
    heads = jnp.asarray(h0) + jnp.arange(h)

    def per_slot(key):
        def per_head(hi):
            kh = jax.random.fold_in(key, hi)
            return draw_comparator_prns(kh, (t, 1, l), (t, 1, d), d, i_max)

        rs, ra = jax.vmap(per_head)(heads)  # [H, T, 1, *]
        return (jnp.moveaxis(rs, 0, 1).reshape(t * h, 1, l),
                jnp.moveaxis(ra, 0, 1).reshape(t * h, 1, d))

    return jax.vmap(per_slot)(slot_keys)


@partial(jax.jit, static_argnames=("i_max", "interpret"))
def ssa_attention_decode_packed(
    q: Array,  # [T, B, H, 1, D] binary — the new token's query spikes
    k: Array,  # [T, B, H, L, D] cached key spike train (zeros beyond pos)
    v: Array,  # [T, B, H, L, D] cached value spike train
    slot_keys: Array,  # [B, 2] uint32 per-slot PRNG keys
    h0: Union[int, Array] = 0,  # global index of q's first head (TP shards)
    *,
    i_max: int,
    interpret: Optional[bool] = None,
) -> Array:
    """Bit-packed SSA decode step; returns uint8 spikes [T,B,H,1,D].

    The serving entry point for the popcount SSA tile: one query row per
    (slot, timestep, head) against that slot's cached KV train.  L and D
    are zero-padded to multiples of 32 (zero spikes never beat a
    comparator draw, exactly the :func:`ssa_attention_packed` argument);
    the comparator PRNs are drawn per (slot, global head) at logical
    shapes so the output is bit-identical to the unpadded integer oracle —
    independent of which other slots are in flight *and* of how the heads
    are sharded across a mesh (``h0`` names the shard's first global head;
    it may be traced, e.g. ``lax.axis_index(...) * h_local``).
    """
    t, b, h, n1, d = q.shape
    l = k.shape[3]
    rs, ra = draw_slot_decode_prns(slot_keys, t, h, l, d, i_max, h0)
    g = b * t * h
    # grid order (b, t, h): matches the [B, T*H, ...] PRN layout
    qf = jnp.moveaxis(q, 1, 0).reshape(g, 1, d).astype(jnp.uint8)
    kf = jnp.moveaxis(k, 1, 0).reshape(g, l, d).astype(jnp.uint8)
    vf = jnp.moveaxis(v, 1, 0).reshape(g, l, d).astype(jnp.uint8)
    rs = rs.reshape(g, 1, l)
    ra = ra.reshape(g, 1, d)
    l_pad = (-l) % 32
    d_pad = (-d) % 32
    if l_pad or d_pad:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, d_pad)))
        kf = jnp.pad(kf, ((0, 0), (0, l_pad), (0, d_pad)))
        vf = jnp.pad(vf, ((0, 0), (0, l_pad), (0, d_pad)))
        rs = jnp.pad(rs, ((0, 0), (0, 0), (0, l_pad)))
        ra = jnp.pad(ra, ((0, 0), (0, 0), (0, d_pad)))
    qp = pack_bits(qf, axis=-1)  # [G, 1, D/32]
    kp = pack_bits(kf, axis=-1)  # [G, L, D/32]
    vp = pack_bits(vf, axis=-2)  # [G, L/32, D]
    out = ssa_decode_kernel(qp, kp, vp, rs, ra,
                            interpret=interpret_mode(interpret))
    out = out[:, :, :d].reshape(b, t, h, 1, d)
    return jnp.moveaxis(out, 0, 1)


def gather_kv_pages(pool: Array, page_table: Array) -> Array:
    """Paged pool -> dense per-slot KV view (the non-kernel backends' path).

    ``pool [P, T, KV, page_len, d]`` + ``page_table [B, MP]`` -> ``[T, B,
    KV, MP*page_len, d]``: page ``table[b, j]`` lands at logical positions
    ``[j*page_len, (j+1)*page_len)`` of slot ``b``.  Table entry 0 is the
    permanently-zero null page, so unallocated blocks read as zero spikes
    (comparator-masked for free)."""
    g = pool[page_table]  # [B, MP, T, KV, page_len, d]
    g = jnp.moveaxis(g, 2, 0)  # [T, B, MP, KV, page_len, d]
    g = jnp.swapaxes(g, 2, 3)  # [T, B, KV, MP, page_len, d]
    return g.reshape(g.shape[:3] + (-1, g.shape[-1]))


@partial(jax.jit, static_argnames=("i_max", "interpret"))
def ssa_attention_decode_paged_packed(
    q: Array,  # [T, B, H, 1, D] binary — the new tokens' query spikes
    kpool: Array,  # [P, T, KV, page_len, D] key spike page pool
    vpool: Array,  # [P, T, KV, page_len, D] value spike page pool
    page_table: Array,  # [B, MP] int32 page ids (0 = null page)
    slot_keys: Array,  # [B, 2] uint32 per-slot PRNG keys
    h0: Union[int, Array] = 0,  # global index of q's first head (TP shards)
    *,
    i_max: int,
    interpret: Optional[bool] = None,
) -> Array:
    """Bit-packed *paged* SSA decode step; returns uint8 spikes [T,B,H,1,D].

    The block-paged serving entry point: K/V spike trains live in a global
    physical page pool and each slot addresses its blocks through a page
    table, which the kernel dereferences via scalar-prefetch index maps —
    no dense per-slot cache is ever materialised in the kernel's address
    stream.  The comparator PRNs are drawn per (slot, global head) at the
    *logical* cache geometry ``L = MP * page_len`` with the same
    ``f(seed, pos, head)`` streams as the dense path, so paged decode is
    bit-identical to :func:`ssa_attention_decode_packed` over the
    materialised cache (and to the integer oracle
    :func:`repro.kernels.ref.ssa_decode_paged_ref`).  In-page position and
    spike-lane padding to 32-lane multiples is zero-filled: padded
    positions pair zero K spikes with zero comparator draws, and ``0 > 0``
    never fires, so they contribute nothing.  GQA repeats KV heads inside
    the kernel's index maps instead of materialising repeated pools."""
    t, b, h, n1, d = q.shape
    pl_ = kpool.shape[3]
    mp = page_table.shape[1]
    l = mp * pl_
    rs, ra = draw_slot_decode_prns(slot_keys, t, h, l, d, i_max, h0)
    # pad the in-page position axis and the spike-lane axis to 32-multiples
    p_pad = (-pl_) % 32
    d_pad = (-d) % 32
    plp, dp = pl_ + p_pad, d + d_pad
    qf = jnp.moveaxis(q, 1, 0).reshape(b, t, h, 1, d).astype(jnp.uint8)
    kf = kpool.astype(jnp.uint8)
    vf = vpool.astype(jnp.uint8)
    rs = rs.reshape(b, t, h, 1, mp, pl_)
    ra = ra.reshape(b, t, h, 1, d)
    if p_pad or d_pad:
        qf = jnp.pad(qf, ((0, 0),) * 4 + ((0, d_pad),))
        kf = jnp.pad(kf, ((0, 0),) * 3 + ((0, p_pad), (0, d_pad)))
        vf = jnp.pad(vf, ((0, 0),) * 3 + ((0, p_pad), (0, d_pad)))
        rs = jnp.pad(rs, ((0, 0),) * 5 + ((0, p_pad),))
        ra = jnp.pad(ra, ((0, 0),) * 4 + ((0, d_pad),))
    # one [1, PLp] row per page, in its own leading slot: a TPU block must
    # span the whole of the last two axes or align with the (8, 128) tile
    rs = rs.reshape(b, t, h, mp, 1, plp)
    qp = pack_bits(qf, axis=-1)  # [B, T, H, 1, Wd]
    kp = pack_bits(kf, axis=-1)  # [P, T, KV, PLp, Wd]
    vp = pack_bits(vf, axis=-2)  # [P, T, KV, Wp, Dp]
    out = ssa_decode_paged_kernel(
        page_table.astype(jnp.int32), qp, kp, vp, rs, ra,
        interpret=interpret_mode(interpret))
    return jnp.moveaxis(out[..., :d], 0, 1)  # [T, B, H, 1, D]


@partial(jax.jit, static_argnames=("beta", "v_thresh", "interpret"))
def lif_fused(currents: Array, *, beta: float = 0.5, v_thresh: float = 1.0,
              interpret: Optional[bool] = None) -> Array:
    """Fused LIF over [T, ...] currents; returns uint8 spikes.

    The feature axis is flattened, zero-padded and laid out as 128-lane
    rows in blocks of up to 256 rows (a multiple of the 32-row uint8
    tile)."""
    t = currents.shape[0]
    flat = currents.reshape(t, -1)
    m = flat.shape[1]
    rows = _rup(-(-m // 128), 32)
    block_rows = min(256, rows)
    rows = _rup(rows, block_rows)
    flat = jnp.pad(flat, ((0, 0), (0, rows * 128 - m)))
    out = lif_kernel(flat.reshape(t, rows, 128), beta=beta, v_thresh=v_thresh,
                     block_rows=block_rows, interpret=interpret_mode(interpret))
    return out.reshape(t, rows * 128)[:, :m].reshape(currents.shape)


@partial(jax.jit, static_argnames=("beta", "v_thresh", "interpret"))
def aimc_spiking_linear(
    spikes: Array,  # [T, B, d_in]
    w_levels: Array,  # [d_in, d_out] int8
    scale: Array,  # [d_out]
    bias: Optional[Array] = None,  # [d_out] digital per-column bias
    *,
    beta: float = 0.5,
    v_thresh: float = 1.0,
    interpret: Optional[bool] = None,
) -> Array:
    t, b, d_in = spikes.shape
    d_out = w_levels.shape[1]

    bb = _rup(b, 8) if b < 128 else _rup(b, 128)
    di = _rup(d_in, 128)
    do = _rup(d_out, 128)
    sp = jnp.pad(spikes, ((0, 0), (0, bb - b), (0, di - d_in)))
    wp = jnp.pad(w_levels, ((0, di - d_in), (0, do - d_out)))
    sc = jnp.pad(scale, (0, do - d_out)).reshape(1, do)
    if bias is None:
        bi = jnp.zeros((1, do), jnp.float32)
    else:
        bi = jnp.pad(bias.astype(jnp.float32), (0, do - d_out)).reshape(1, do)
    out = aimc_spiking_linear_kernel(
        sp, wp, sc, bi, beta=beta, v_thresh=v_thresh,
        block_b=min(bb, 128), block_in=128, block_out=128,
        interpret=interpret_mode(interpret),
    )
    return out[:, :b, :d_out]


@partial(jax.jit, static_argnames=("interpret",))
def aimc_matmul_counts(
    spikes: Array,  # [T, B, d_in]
    w_levels: Array,  # [d_in, d_out] int8
    *,
    interpret: Optional[bool] = None,
) -> Array:
    """[T, B, d_out] f32 integer-valued crossbar counts (pre-scale/LIF).

    The shard-local programmed-AIMC matmul of a *row-parallel* spiking
    linear: each mesh shard runs this over its d_in rows, the counts psum
    across the ``model`` axis (exact — integer-valued f32), and scale/bias/
    LIF fire once on the reduced currents.  Zero-padded to kernel block
    multiples and sliced back, like :func:`aimc_spiking_linear`."""
    t, b, d_in = spikes.shape
    d_out = w_levels.shape[1]

    bb = _rup(b, 8) if b < 128 else _rup(b, 128)
    di = _rup(d_in, 128)
    do = _rup(d_out, 128)
    sp = jnp.pad(spikes, ((0, 0), (0, bb - b), (0, di - d_in)))
    wp = jnp.pad(w_levels, ((0, di - d_in), (0, do - d_out)))
    out = aimc_matmul_counts_kernel(
        sp, wp, block_b=min(bb, 128), block_in=128, block_out=128,
        interpret=interpret_mode(interpret),
    )
    return out[:, :b, :d_out]


@partial(jax.jit, static_argnames=("t0", "img_gain", "interpret"))
def drift_requantize(
    levels: Array,  # [d_in, d_out] f32 programmed integer levels
    eps: Array,  # [d_in, d_out] f32 frozen programming error (level units)
    nu: Array,  # [d_in, d_out] f32 per-device drift exponents
    t_seconds: Array,  # scalar f32 device time (traced)
    *,
    t0: float,
    img_gain: int = 1,
    interpret: Optional[bool] = None,
) -> Array:
    """Drifted-conductance requantisation on the Pallas path.

    The calibration-time fold that keeps the programmed-state hot loop an
    int8 MXU matmul: re-digitise ``(levels+eps) * (t/t0)^-nu * img_gain``
    onto the full int8 image grid.  Zero-padded to 128x128 tile multiples and sliced
    back; bit-exact vs :func:`repro.kernels.ref.drift_requantize_ref` (and
    ``repro.aimc_device.drift_to``) for any shape."""
    d_in, d_out = levels.shape

    di, do = _rup(d_in, 128), _rup(d_out, 128)
    pad = ((0, di - d_in), (0, do - d_out))
    out = drift_requantize_kernel(
        jnp.pad(levels.astype(jnp.float32), pad),
        jnp.pad(eps.astype(jnp.float32), pad),
        jnp.pad(nu.astype(jnp.float32), pad),
        jnp.reshape(t_seconds, (1,)).astype(jnp.float32),
        t0=t0, img_gain=img_gain, interpret=interpret_mode(interpret),
    )
    return out[:d_in, :d_out]


@partial(jax.jit, static_argnames=("t0", "img_gain", "beta", "v_thresh",
                                   "interpret"))
def aimc_spiking_linear_programmed(
    spikes: Array,  # [T, B, d_in]
    levels: Array,  # [d_in, d_out] f32 programmed integer levels
    eps: Array,  # [d_in, d_out] f32 frozen programming error
    nu: Array,  # [d_in, d_out] f32 per-device drift exponents
    scale: Array,  # [d_out] f32 programmed per-column scale
    t_seconds: Array,  # scalar device time
    gdc_gain: Array,  # scalar GDC gain (stale between recalibrations)
    bias: Optional[Array] = None,
    *,
    t0: float,
    img_gain: int = 1,
    beta: float = 0.5,
    v_thresh: float = 1.0,
    interpret: Optional[bool] = None,
) -> Array:
    """End-to-end programmed-state spiking linear on the Pallas path.

    Fold kernel (:func:`drift_requantize`) + int8 matmul/LIF kernel
    (:func:`aimc_spiking_linear`); bit-exact vs
    :func:`repro.kernels.ref.aimc_programmed_linear_ref` at fixed
    ``t_seconds``.  Production serving keeps the folded ``levels_t`` /
    ``eff_scale`` cached in :class:`repro.aimc_device.AIMCDeviceState` and
    calls :func:`aimc_spiking_linear` directly; this wrapper is the
    one-shot (fold-on-the-fly) variant used by tests and drift studies."""
    levels_t = drift_requantize(levels, eps, nu, t_seconds, t0=t0,
                                img_gain=img_gain, interpret=interpret)
    eff_scale = (scale * gdc_gain / float(img_gain)).astype(jnp.float32)
    return aimc_spiking_linear(spikes, levels_t, eff_scale, bias, beta=beta,
                               v_thresh=v_thresh, interpret=interpret)
