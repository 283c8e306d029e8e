"""Property-based differential fuzzing of the backend matrix.

Hypothesis-driven random shapes / timesteps / seeds asserting the engine's
substrate-interchangeability contract op by op:

* **pallas == integer, bit-exact, always** — the packed popcount kernels
  (full-sequence SSA, dense decode, *paged* decode with h0 head offsets)
  and the fused crossbar/LIF kernels reproduce the integer oracle exactly
  for any shape, including the padding paths (non-multiple-of-32 lane and
  position axes, GQA head groups).
* **reference joins the bit-exact set where its float math is exact** —
  LIF over identical currents, drift re-quantisation (deterministic), and
  ``spiking_linear`` whenever the float weights are exactly representable
  on the quantisation grid with a power-of-two column scale (every partial
  product and sum is then a dyadic rational inside the f32 mantissa, so
  reference == integer == pallas bit-for-bit).  For the stochastic SSA ops
  the reference backend draws *uniform-float* comparators rather than the
  LFSR integers, so it is distribution-equal but not bit-equal — those
  assertions stop at the digital pair by design (see ``repro.engine``).

Each property explores randomised hypothesis examples.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.engine import IntegerBackend, PallasBackend, ReferenceBackend
from repro.kernels import ops as KOPS
from repro.kernels import ref as KREF
from repro.kernels.plan import AttnSpec, KVView

INT = IntegerBackend()
PAL = PallasBackend()
REF = ReferenceBackend()

_SET = dict(max_examples=8, deadline=None)


def _key(seed):
    return jax.random.PRNGKey(seed)


def _bern(key, p, shape):
    return jax.random.bernoulli(key, p, shape).astype(jnp.uint8)


def _eq(a, b, msg):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ---------------------------------------------------------------------------
# SSA attention (full sequence)
# ---------------------------------------------------------------------------


@settings(**_SET)
@given(t=st.integers(1, 3), n=st.integers(1, 21), d=st.sampled_from([8, 16, 33]),
       h=st.integers(1, 2), seed=st.integers(0, 2**31 - 1),
       causal=st.booleans())
def test_ssa_attention_pallas_matches_integer(t, n, d, h, seed, causal):
    """Packed popcount SSA == integer oracle for arbitrary (T, N, D, H),
    causal or not — including N/D that exercise the zero-pad lanes."""
    ks = jax.random.split(_key(seed), 4)
    q = _bern(ks[0], 0.5, (t, 1, h, n, d))
    k = _bern(ks[1], 0.4, (t, 1, h, n, d))
    v = _bern(ks[2], 0.6, (t, 1, h, n, d))
    out_i = INT.ssa_attention(ks[3], q, k, v, causal=causal)
    out_p = PAL.ssa_attention(ks[3], q, k, v, causal=causal)
    _eq(out_i, out_p, f"ssa_attention t={t} n={n} d={d} causal={causal}")


# ---------------------------------------------------------------------------
# SSA decode — dense and paged, with TP head offsets
# ---------------------------------------------------------------------------


@settings(**_SET)
@given(t=st.integers(1, 3), l=st.sampled_from([4, 16, 33]),
       d=st.sampled_from([8, 16]), h=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 2**31 - 1), h0=st.integers(0, 5))
def test_ssa_decode_pallas_matches_integer_with_h0(t, l, d, h, seed, h0):
    """Dense decode kernel == integer oracle for any cache length / head
    count, and the ``h0`` global-head offset selects exactly the oracle's
    PRN rows (the tensor-parallel shard contract)."""
    ks = jax.random.split(_key(seed), 4)
    b = 2
    q = _bern(ks[0], 0.5, (t, b, h, 1, d))
    k = _bern(ks[1], 0.4, (t, b, h, l, d))
    v = _bern(ks[2], 0.5, (t, b, h, l, d))
    slot_keys = jax.random.randint(ks[3], (b, 2), 0, 2**31 - 1,
                                   jnp.int32).astype(jnp.uint32)
    view = KVView.dense(k, v)
    out_i = INT.decode_attention(view, q, AttnSpec(i_max=l, h0=h0),
                                 slot_keys=slot_keys)
    out_p = PAL.decode_attention(view, q, AttnSpec(i_max=l, h0=h0),
                                 slot_keys=slot_keys)
    _eq(out_i, out_p, f"ssa_decode t={t} l={l} d={d} h={h} h0={h0}")
    if h % 2 == 0:  # sharding by heads reproduces the full call exactly
        half = h // 2
        parts = [
            PAL.decode_attention(
                KVView.dense(k[:, :, s * half:(s + 1) * half],
                             v[:, :, s * half:(s + 1) * half]),
                q[:, :, s * half:(s + 1) * half],
                AttnSpec(i_max=l, h0=h0 + s * half), slot_keys=slot_keys)
            for s in range(2)
        ]
        _eq(jnp.concatenate(parts, axis=2), out_p, "h0 shard split diverged")


@settings(**_SET)
@given(t=st.integers(1, 3), page_len=st.sampled_from([4, 8, 32]),
       mp=st.integers(1, 4), d=st.sampled_from([8, 16]),
       hkv=st.sampled_from([(1, 1), (2, 1), (4, 2)]),
       seed=st.integers(0, 2**31 - 1), h0=st.integers(0, 3))
def test_ssa_decode_paged_matches_integer_and_dense(t, page_len, mp, d, hkv,
                                                    seed, h0):
    """Paged decode (scalar-prefetch page gathering) == the paged integer
    oracle == the dense decode over the materialised cache, for any page
    geometry, GQA grouping, null-page pattern and head offset."""
    h, kv = hkv
    ks = jax.random.split(_key(seed), 6)
    b = 2
    n_pages = 2 + b * mp
    q = _bern(ks[0], 0.5, (t, b, h, 1, d))
    kpool = _bern(ks[1], 0.4, (n_pages, t, kv, page_len, d))
    vpool = _bern(ks[2], 0.5, (n_pages, t, kv, page_len, d))
    kpool = kpool.at[0].set(0)  # null page invariant
    vpool = vpool.at[0].set(0)
    table = jax.random.randint(ks[3], (b, mp), 0, n_pages, jnp.int32)
    table = jnp.where(jax.random.bernoulli(ks[4], 0.3, (b, mp)), 0, table)
    slot_keys = jax.random.randint(ks[5], (b, 2), 0, 2**31 - 1,
                                   jnp.int32).astype(jnp.uint32)
    i_max = mp * page_len
    view = KVView.from_pool(kpool, vpool, table)
    spec = AttnSpec(i_max=i_max, h0=h0, groups=h // kv)
    out_i = INT.decode_attention(view, q, spec, slot_keys=slot_keys)
    out_p = PAL.decode_attention(view, q, spec, slot_keys=slot_keys)
    _eq(out_i, out_p, f"paged decode pl={page_len} mp={mp} h={h} kv={kv}")
    # dense equivalence over the gathered view
    kf = KOPS.gather_kv_pages(kpool, table)
    vf = KOPS.gather_kv_pages(vpool, table)
    if kv != h:
        kf = jnp.repeat(kf, h // kv, axis=2)
        vf = jnp.repeat(vf, h // kv, axis=2)
    dense = INT.decode_attention(KVView.dense(kf, vf), q,
                                 AttnSpec(i_max=i_max, h0=h0),
                                 slot_keys=slot_keys)
    _eq(out_p, dense, "paged != dense over materialised cache")


# ---------------------------------------------------------------------------
# Fused decode layer (one megakernel per decoder layer) vs the integer oracle
# ---------------------------------------------------------------------------


def _layer_ws(key, dims, bias):
    """One dyadic-grid weight dict per (d_in, d_out) stage — quarter-grid
    biases keep every backend's arithmetic exact (see module docstring)."""
    out = []
    for i, (di, do) in enumerate(dims):
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        b = (jax.random.randint(kb, (do,), -4, 5, jnp.int32)
             .astype(jnp.float32) * 0.25) if bias else None
        out.append({"w": _dyadic_weights(kw, di, do), "b": b})
    return out


def _slice_cols(p, lo, hi):
    """Column-shard one weight dict (what the TP shards hold)."""
    return {"w": p["w"][:, lo:hi],
            "b": None if p["b"] is None else p["b"][lo:hi]}


@settings(**_SET)
@given(t=st.integers(1, 2), l=st.sampled_from([4, 16]),
       hkv=st.sampled_from([(2, 2), (4, 2)]),
       seed=st.integers(0, 2**31 - 1), bias=st.booleans(), mlp=st.booleans())
def test_fused_decode_layer_dense_matches_integer_oracle(t, l, hkv, seed,
                                                         bias, mlp):
    """The dense megakernel == the integer fused-layer oracle bit-for-bit
    (residual out AND new K/V trains) for any cache length, GQA grouping,
    bias/MLP combination — and the head-sharded ``h0`` split of the
    attention stage (column-sliced Q/K/V, ``with_tail=False``) concatenates
    to the full launch exactly (the tensor-parallel shard contract)."""
    h, kv = hkv
    d, hd, d_ff = 16, 8, 24
    b = 2
    ks = jax.random.split(_key(seed), 5)
    s = _bern(ks[0], 0.5, (t, b, d)).astype(jnp.float32)
    pos = jax.random.randint(ks[1], (b,), 0, l, jnp.int32)
    live = (jnp.arange(l)[None, :] < pos[:, None]).astype(jnp.uint8)
    sk = _bern(ks[2], 0.4, (b, t, l, kv, hd)) * live[:, None, :, None, None]
    sv = _bern(ks[3], 0.5, (b, t, l, kv, hd)) * live[:, None, :, None, None]
    slot_keys = jax.random.randint(ks[4], (b, 2), 0, 2**31 - 1,
                                   jnp.int32).astype(jnp.uint32)
    wq, wk, wv, wo, wi, wo2 = _layer_ws(
        _key(seed ^ 0xA5A5), [(d, h * hd), (d, kv * hd), (d, kv * hd),
                              (h * hd, d), (d, d_ff), (d_ff, d)], bias)
    view = KVView.dense(sk, sv)
    args = (slot_keys, s, view, pos, wq, wk, wv, wo, wi, wo2)
    out_i = INT.decode_layer_fused(*args, hd=hd, with_mlp=mlp)
    out_p = PAL.decode_layer_fused(*args, hd=hd, with_mlp=mlp)
    for gi, gp, name in zip(out_i, out_p, ("s_out", "k_new", "v_new")):
        _eq(gi, gp, f"fused dense {name} t={t} l={l} h={h} kv={kv}")
    # attention-stage building block + TP h0 shard split
    a_full, kn, vn = PAL.decode_layer_fused(
        slot_keys, s, view, pos, wq, wk, wv, hd=hd, with_tail=False)
    _eq(a_full, INT.decode_layer_fused(
        slot_keys, s, view, pos, wq, wk, wv, hd=hd, with_tail=False)[0],
        "fused with_tail=False diverged from oracle")
    hloc, kvloc = h // 2, kv // 2
    parts = [
        PAL.decode_layer_fused(
            slot_keys, s,
            KVView.dense(sk[:, :, :, sh * kvloc:(sh + 1) * kvloc],
                         sv[:, :, :, sh * kvloc:(sh + 1) * kvloc]),
            pos,
            _slice_cols(wq, sh * hloc * hd, (sh + 1) * hloc * hd),
            _slice_cols(wk, sh * kvloc * hd, (sh + 1) * kvloc * hd),
            _slice_cols(wv, sh * kvloc * hd, (sh + 1) * kvloc * hd),
            hd=hd, h0=sh * hloc, with_tail=False)
        for sh in range(2)
    ]
    _eq(jnp.concatenate([p[0] for p in parts], axis=-1), a_full,
        "h0 shard split of fused attention stage diverged")
    _eq(jnp.concatenate([p[1] for p in parts], axis=2), kn,
        "h0 shard split of fused k_new diverged")
    _eq(jnp.concatenate([p[2] for p in parts], axis=2), vn,
        "h0 shard split of fused v_new diverged")


@settings(**_SET)
@given(t=st.integers(1, 2), page_len=st.sampled_from([4, 8]),
       mp=st.integers(1, 3), hkv=st.sampled_from([(2, 2), (4, 2)]),
       seed=st.integers(0, 2**31 - 1), mlp=st.booleans())
def test_fused_decode_layer_paged_matches_integer_oracle(t, page_len, mp,
                                                         hkv, seed, mlp):
    """The paged megakernel (scalar-prefetch page-table grid) == the paged
    integer fused-layer oracle for any page geometry, null-page pattern and
    GQA grouping — under the serving invariants the scheduler maintains
    (exclusive write pages, zero pre-scatter write slot)."""
    h, kv = hkv
    d, hd, d_ff = 16, 8, 24
    b = 2
    l = mp * page_len
    n_pages = 4 + b * mp
    ks = jax.random.split(_key(seed), 6)
    s = _bern(ks[0], 0.5, (t, b, d)).astype(jnp.float32)
    kpool = _bern(ks[1], 0.4, (n_pages, t, kv, page_len, hd))
    vpool = _bern(ks[2], 0.5, (n_pages, t, kv, page_len, hd))
    kpool = kpool.at[0].set(0)  # null page invariant
    vpool = vpool.at[0].set(0)
    # random shared read-only pages (>= 4) + null holes; each slot's write
    # page (2 / 3) is exclusively owned, as CoW guarantees in serving
    table = jax.random.randint(ks[3], (b, mp), 4, n_pages, jnp.int32)
    table = jnp.where(jax.random.bernoulli(ks[4], 0.3, (b, mp)), 0, table)
    pos = jax.random.randint(ks[5], (b,), 0, l, jnp.int32)
    barange = jnp.arange(b)
    write_pids = jnp.asarray([2, 3], jnp.int32)
    table = table.at[barange, pos // page_len].set(write_pids)
    off = pos % page_len
    kpool = kpool.at[write_pids, :, :, off].set(0)  # pre-scatter zero slot
    vpool = vpool.at[write_pids, :, :, off].set(0)
    slot_keys = jax.random.randint(jax.random.fold_in(ks[5], 1), (b, 2), 0,
                                   2**31 - 1, jnp.int32).astype(jnp.uint32)
    wq, wk, wv, wo, wi, wo2 = _layer_ws(
        _key(seed ^ 0x5A5A), [(d, h * hd), (d, kv * hd), (d, kv * hd),
                              (h * hd, d), (d, d_ff), (d_ff, d)], True)
    view = KVView.from_pool(kpool, vpool, table)
    args = (slot_keys, s, view, pos, wq, wk, wv, wo, wi, wo2)
    kw = dict(hd=hd, write_pids=write_pids, with_mlp=mlp)
    out_i = INT.decode_layer_fused(*args, **kw)
    out_p = PAL.decode_layer_fused(*args, **kw)
    for gi, gp, name in zip(out_i, out_p, ("s_out", "k_new", "v_new")):
        _eq(gi, gp, f"fused paged {name} pl={page_len} mp={mp} h={h} kv={kv}")
    # TP h0 shard split over the pool's KV axis, with_tail=False
    a_full = PAL.decode_layer_fused(
        slot_keys, s, view, pos, wq, wk, wv, hd=hd, write_pids=write_pids,
        with_tail=False)[0]
    hloc, kvloc = h // 2, kv // 2
    parts = [
        PAL.decode_layer_fused(
            slot_keys, s,
            KVView.from_pool(kpool[:, :, sh * kvloc:(sh + 1) * kvloc],
                             vpool[:, :, sh * kvloc:(sh + 1) * kvloc], table),
            pos,
            _slice_cols(wq, sh * hloc * hd, (sh + 1) * hloc * hd),
            _slice_cols(wk, sh * kvloc * hd, (sh + 1) * kvloc * hd),
            _slice_cols(wv, sh * kvloc * hd, (sh + 1) * kvloc * hd),
            hd=hd, h0=sh * hloc, write_pids=write_pids, with_tail=False)[0]
        for sh in range(2)
    ]
    _eq(jnp.concatenate(parts, axis=-1), a_full,
        "h0 shard split of paged fused attention stage diverged")


# ---------------------------------------------------------------------------
# Deprecated decode shims — warn, and forward bit-exactly
# ---------------------------------------------------------------------------


def test_decode_shims_warn_and_forward_bit_exactly():
    """``ssa_attention_decode`` / ``ssa_attention_decode_paged`` are
    deprecation shims over ``decode_attention(view, q, spec)``: every
    backend emits DeprecationWarning and returns the exact same bits."""
    t, b, h, l, d = 2, 2, 2, 8, 16
    ks = jax.random.split(_key(0), 6)
    q = _bern(ks[0], 0.5, (t, b, h, 1, d))
    k = _bern(ks[1], 0.4, (t, b, h, l, d))
    v = _bern(ks[2], 0.5, (t, b, h, l, d))
    slot_keys = jax.random.randint(ks[3], (b, 2), 0, 2**31 - 1,
                                   jnp.int32).astype(jnp.uint32)
    for be in (REF, INT, PAL):
        with pytest.warns(DeprecationWarning, match="decode_attention"):
            old = be.ssa_attention_decode(slot_keys, q, k, v, i_max=l, h0=1)
        new = be.decode_attention(KVView.dense(k, v), q,
                                  AttnSpec(i_max=l, h0=1),
                                  slot_keys=slot_keys)
        _eq(old, new, f"{be.name} dense decode shim diverged")
    page_len, mp = 4, 2
    kpool = _bern(ks[4], 0.4, (2 + b * mp, t, h, page_len, d))
    vpool = _bern(ks[5], 0.5, (2 + b * mp, t, h, page_len, d))
    kpool = kpool.at[0].set(0)
    vpool = vpool.at[0].set(0)
    table = jnp.asarray([[2, 3], [4, 0]], jnp.int32)
    for be in (REF, INT, PAL):
        with pytest.warns(DeprecationWarning, match="decode_attention"):
            old = be.ssa_attention_decode_paged(slot_keys, q, kpool, vpool,
                                                table, i_max=mp * page_len)
        new = be.decode_attention(KVView.from_pool(kpool, vpool, table), q,
                                  AttnSpec(i_max=mp * page_len, groups=1),
                                  slot_keys=slot_keys)
        _eq(old, new, f"{be.name} paged decode shim diverged")


# ---------------------------------------------------------------------------
# Spiking linear (crossbar MVM + LIF) — col/row parts, all three backends
# ---------------------------------------------------------------------------


def _dyadic_weights(key, d_in, d_out, levels=15, scale=2.0**-3):
    """Float weights exactly on the quantisation grid with a power-of-two
    column scale: every backend's arithmetic is then exact, so reference
    == integer == pallas bit-for-bit (see module docstring)."""
    lv = jax.random.randint(key, (d_in, d_out), -levels, levels + 1, jnp.int32)
    lv = lv.at[0].set(levels)  # pin each column's amax to `levels`
    return (lv * scale).astype(jnp.float32)


@settings(**_SET)
@given(t=st.integers(1, 4), b=st.integers(1, 3),
       d_in=st.sampled_from([8, 24, 64]), d_out=st.sampled_from([16, 33]),
       part=st.sampled_from(["col", "row"]), seed=st.integers(0, 2**31 - 1),
       bias=st.booleans())
def test_spiking_linear_all_backends_bit_exact(t, b, d_in, d_out, part, seed,
                                               bias):
    """LIF(W s) over binary trains: all THREE backends agree bit-for-bit on
    dyadic-grid weights, for both tensor-parallel part hints and arbitrary
    (T, B, d_in, d_out) incl. pad paths."""
    ks = jax.random.split(_key(seed), 3)
    spikes = _bern(ks[0], 0.5, (t, b, d_in)).astype(jnp.float32)
    w = _dyadic_weights(ks[1], d_in, d_out)
    p = {"w": w, "b": (jnp.arange(d_out, dtype=jnp.float32) * 0.25
                       if bias else None)}
    out_r = REF.spiking_linear(None, p, spikes, part=part)
    out_i = INT.spiking_linear(None, p, spikes, part=part)
    out_p = PAL.spiking_linear(None, p, spikes, part=part)
    _eq(out_i, out_p, f"integer != pallas ({t},{b},{d_in},{d_out},{part})")
    _eq(out_r.astype(jnp.uint8), out_i,
        f"reference != integer on dyadic grid ({t},{b},{d_in},{d_out})")


@settings(**_SET)
@given(t=st.integers(1, 3), d_in=st.sampled_from([16, 48]),
       d_out=st.sampled_from([16, 40]), seed=st.integers(0, 2**31 - 1))
def test_spiking_linear_row_counts_psum_decomposition(t, d_in, d_out, seed):
    """The row-parallel decomposition contract: shard-local integer counts
    summed across an input-row split reproduce the fused kernel exactly
    (what ``distributed.ShardedBackend`` relies on for ``part='row'``)."""
    ks = jax.random.split(_key(seed), 3)
    spikes = _bern(ks[0], 0.5, (t, 2, d_in)).astype(jnp.float32)
    levels = jax.random.randint(ks[1], (d_in, d_out), -15, 16,
                                jnp.int32).astype(jnp.int8)
    scale = (jax.random.randint(ks[2], (d_out,), 1, 8, jnp.int32)
             .astype(jnp.float32) * 0.125)
    half = d_in // 2
    counts = (KOPS.aimc_matmul_counts(spikes[..., :half], levels[:half])
              + KOPS.aimc_matmul_counts(spikes[..., half:], levels[half:]))
    pre = counts * scale[None, None, :]
    split = KREF.lif_ref(pre.reshape(t, -1)).reshape(pre.shape)
    fused = KREF.aimc_spiking_linear_ref(spikes, levels, scale)
    _eq(split, fused, "row-split counts diverged from fused kernel")


# ---------------------------------------------------------------------------
# Drift re-quantisation (deterministic: kernel == oracle everywhere)
# ---------------------------------------------------------------------------


@settings(**_SET)
@given(d_in=st.sampled_from([8, 130]), d_out=st.sampled_from([16, 129]),
       t_s=st.sampled_from([0.0, 25.0, 3600.0, 86400.0]),
       img_gain=st.sampled_from([1, 4]), seed=st.integers(0, 2**31 - 1))
def test_drift_requantize_kernel_matches_ref(d_in, d_out, t_s, img_gain, seed):
    """The Pallas drift-fold kernel re-digitises drifted conductances onto
    the int8 image grid bit-identically to the oracle for any shape
    (incl. >1 tile), device age and image gain."""
    ks = jax.random.split(_key(seed), 3)
    levels = jax.random.randint(ks[0], (d_in, d_out), -15, 16,
                                jnp.int32).astype(jnp.float32)
    eps = 0.3 * jax.random.normal(ks[1], (d_in, d_out), jnp.float32)
    nu = 0.05 + 0.02 * jax.random.normal(ks[2], (d_in, d_out), jnp.float32)
    got = KOPS.drift_requantize(levels, eps, nu, jnp.float32(t_s), t0=1.0,
                                img_gain=img_gain)
    want = KREF.drift_requantize_ref(levels, eps, nu, t_s, t0=1.0,
                                     img_gain=img_gain)
    _eq(got, want, f"drift_requantize ({d_in},{d_out},t={t_s},g={img_gain})")


# ---------------------------------------------------------------------------
# LIF (deterministic: all three substrates)
# ---------------------------------------------------------------------------


@settings(**_SET)
@given(t=st.integers(1, 6), m=st.sampled_from([1, 7, 300]),
       seed=st.integers(0, 2**31 - 1))
def test_lif_all_backends_bit_exact(t, m, seed):
    """The fused-membrane kernel, the integer oracle and the reference
    surrogate-gradient LIF all emit identical spikes for identical
    currents (quarter-grid currents keep every membrane value exact)."""
    cur = (jax.random.randint(_key(seed), (t, m), -8, 9, jnp.int32)
           .astype(jnp.float32) * 0.25)
    out_i = INT.lif(cur)
    out_p = PAL.lif(cur)
    out_r = REF.lif(cur)
    _eq(out_i, out_p, f"lif integer != pallas (t={t}, m={m})")
    _eq(out_i, out_r.astype(jnp.uint8), f"lif integer != reference (t={t})")
