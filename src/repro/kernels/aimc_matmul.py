"""Pallas TPU kernel: fused AIMC spiking linear (crossbar MVM + LIF over T).

Maps the paper's spiking-neuron tile (§IV-A) onto the TPU memory
hierarchy:

  PCM crossbar 128x128 tiles       ->  128x128 VMEM weight blocks (int8
                                       5-bit levels, per-column f32 scale)
  O(1) analog MVM                  ->  MXU dot per timestep
  row-block partial sums -> CSA    ->  in-register f32 accumulation over
                                       the d_in grid axis ("arbitrary"
                                       revisiting order, accumulate into
                                       the output block)
  LIF shift-register + comparator  ->  fused membrane update on the last
                                       d_in block — the T non-binary
                                       pre-activations NEVER reach HBM,
                                       which is exactly the row-block-wise
                                       mapping's point (§IV-A-2).

Grid: (batch tiles, d_out tiles, d_in tiles); the d_in axis is the
innermost (sequential) axis so the membrane/current scratch lives in VMEM
across the accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(s_ref, w_ref, scale_ref, bias_ref, out_ref, acc_ref, *, t_steps: int,
            n_in_blocks: int, beta: float, v_thresh: float):
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)  # [bin, bout] int8 levels
    for t in range(t_steps):
        st = s_ref[t].astype(jnp.float32)  # [bb, bin] binary spikes
        acc_ref[t] = acc_ref[t] + jnp.dot(st, w, preferred_element_type=jnp.float32)

    @pl.when(ib == n_in_blocks - 1)
    def _fire():
        scale = scale_ref[...].astype(jnp.float32)  # [1, bout]
        bias = bias_ref[...].astype(jnp.float32)  # [1, bout], digital per-column
        # mirror the oracle's rounding structure exactly (see
        # kernels/ref.py "Float-rounding discipline"): first COMMIT the
        # scaled pre-activations — the store to the VMEM scratch is a
        # materialisation, i.e. one f32 rounding of counts*scale+bias,
        # matching the oracle's pre array — then run the membrane through
        # a loop CARRY (one committed rounding per step, like lax.scan).
        # A fully unrolled chain would let the backend keep the whole
        # T-step recursion at wider precision and flip comparators whose
        # membrane sits within one ulp of v_thresh (found by the
        # property-based differential suite).
        for t in range(t_steps):
            acc_ref[t] = acc_ref[t] * scale + bias

        def step(t, v):
            pre = acc_ref[t]
            v = beta * v + pre
            spike = (v >= v_thresh).astype(jnp.float32)
            # the TPU has no f32 -> u8 conversion: go through int32
            out_ref[t] = spike.astype(jnp.int32).astype(out_ref.dtype)
            return v * (1.0 - spike)

        jax.lax.fori_loop(0, t_steps, step,
                          jnp.zeros(acc_ref.shape[1:], jnp.float32))


def _counts_kernel(s_ref, w_ref, out_ref, acc_ref, *, t_steps: int,
                   n_in_blocks: int):
    """Crossbar MVM accumulation only — no scale/bias/LIF.

    The shard-local half of a *row-parallel* (d_in-sharded) spiking linear:
    each mesh shard accumulates its rows' integer spike counts, the counts
    are psum'd across the ``model`` axis, and the LIF dynamics fire once on
    the reduced currents (see ``repro.distributed.backend``).  Keeping the
    partial sums in integer-valued f32 makes the cross-shard reduction
    exact, so sharded == single-device bit-for-bit."""
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)  # [bin, bout] int8 levels
    for t in range(t_steps):
        st = s_ref[t].astype(jnp.float32)  # [bb, bin] binary spikes
        acc_ref[t] = acc_ref[t] + jnp.dot(st, w, preferred_element_type=jnp.float32)

    @pl.when(ib == n_in_blocks - 1)
    def _flush():
        for t in range(t_steps):
            out_ref[t] = acc_ref[t]


def aimc_matmul_counts_kernel(
    spikes: Array,  # [T, B, d_in] binary (any float/int dtype)
    w_levels: Array,  # [d_in, d_out] int8 conductance levels
    *,
    block_b: int = 128,
    block_in: int = 128,
    block_out: int = 128,
    interpret: bool,
) -> Array:
    """[T, B, d_out] f32 integer-valued crossbar counts (pre-LIF)."""
    t, b, d_in = spikes.shape
    d_out = w_levels.shape[1]
    block_b = min(block_b, b)
    block_in = min(block_in, d_in)
    block_out = min(block_out, d_out)
    assert b % block_b == 0 and d_in % block_in == 0 and d_out % block_out == 0
    nb, ni, no = b // block_b, d_in // block_in, d_out // block_out
    kern = functools.partial(_counts_kernel, t_steps=t, n_in_blocks=ni)
    return pl.pallas_call(
        kern,
        grid=(nb, no, ni),  # d_in innermost: sequential accumulation
        in_specs=[
            pl.BlockSpec((t, block_b, block_in), lambda ib, io, ii: (0, ib, ii)),
            pl.BlockSpec((block_in, block_out), lambda ib, io, ii: (ii, io)),
        ],
        out_specs=pl.BlockSpec((t, block_b, block_out), lambda ib, io, ii: (0, ib, io)),
        out_shape=jax.ShapeDtypeStruct((t, b, d_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((t, block_b, block_out), jnp.float32)],
        interpret=interpret,
    )(spikes, w_levels)


def _requant_kernel(t_ref, lv_ref, eps_ref, nu_ref, out_ref, *, t0: float,
                    img_gain: int):
    """Drift-requantise one [block_in, block_out] crossbar tile.

    The calibration-time fold of the programmed-state path: re-digitise the
    drifted analog conductances ``(levels + eps) * (t/t0)^-nu`` onto the
    full int8 image grid (``img_gain`` steps per programming level), so the
    execution hot loop stays a plain int8 MXU matmul.  The op sequence
    (maximum, exp/log power, gain, round, clip) matches
    ``repro.aimc_device._requantize`` / ``kernels.ref.drift_requantize_ref``
    exactly — bit-exactness of the fold is part of the kernel contract."""
    t = jnp.maximum(t_ref[0], t0)  # scalar from SMEM
    df = jnp.exp(-nu_ref[...] * jnp.log(t / t0))
    g = (lv_ref[...] + eps_ref[...]) * df * float(img_gain)
    out_ref[...] = jnp.clip(jnp.round(g), -127, 127).astype(jnp.int8)


def drift_requantize_kernel(
    levels: Array,  # [d_in, d_out] f32 programmed integer levels
    eps: Array,  # [d_in, d_out] f32 programming error (level units)
    nu: Array,  # [d_in, d_out] f32 per-device drift exponents
    t_seconds: Array,  # [1] f32 device time (traced — no recompile on change)
    *,
    t0: float,
    img_gain: int = 1,
    block_in: int = 128,
    block_out: int = 128,
    interpret: bool,
) -> Array:
    d_in, d_out = levels.shape
    block_in = min(block_in, d_in)
    block_out = min(block_out, d_out)
    assert d_in % block_in == 0 and d_out % block_out == 0
    kern = functools.partial(_requant_kernel, t0=t0, img_gain=img_gain)
    tile = pl.BlockSpec((block_in, block_out), lambda i, j: (i, j))
    return pl.pallas_call(
        kern,
        grid=(d_in // block_in, d_out // block_out),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile, tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((d_in, d_out), jnp.int8),
        interpret=interpret,
    )(t_seconds, levels, eps, nu)


def aimc_spiking_linear_kernel(
    spikes: Array,  # [T, B, d_in] binary (any float/int dtype)
    w_levels: Array,  # [d_in, d_out] int8 (5-bit conductance-pair levels)
    scale: Array,  # [1, d_out] f32 per-column scale
    bias: Array,  # [1, d_out] f32 digital bias added to each timestep's current
    *,
    beta: float = 0.5,
    v_thresh: float = 1.0,
    block_b: int = 128,
    block_in: int = 128,
    block_out: int = 128,
    interpret: bool,
) -> Array:
    t, b, d_in = spikes.shape
    d_out = w_levels.shape[1]
    block_b = min(block_b, b)
    block_in = min(block_in, d_in)
    block_out = min(block_out, d_out)
    assert b % block_b == 0 and d_in % block_in == 0 and d_out % block_out == 0
    nb, ni, no = b // block_b, d_in // block_in, d_out // block_out
    kern = functools.partial(
        _kernel, t_steps=t, n_in_blocks=ni, beta=beta, v_thresh=v_thresh
    )
    return pl.pallas_call(
        kern,
        grid=(nb, no, ni),  # d_in innermost: sequential accumulation
        in_specs=[
            pl.BlockSpec((t, block_b, block_in), lambda ib, io, ii: (0, ib, ii)),
            pl.BlockSpec((block_in, block_out), lambda ib, io, ii: (ii, io)),
            pl.BlockSpec((1, block_out), lambda ib, io, ii: (0, io)),
            pl.BlockSpec((1, block_out), lambda ib, io, ii: (0, io)),
        ],
        out_specs=pl.BlockSpec((t, block_b, block_out), lambda ib, io, ii: (0, ib, io)),
        out_shape=jax.ShapeDtypeStruct((t, b, d_out), jnp.uint8),
        # per-timestep pre-activation accumulator lives in VMEM across the
        # sequential d_in grid axis — never written to HBM
        scratch_shapes=[pltpu.VMEM((t, block_b, block_out), jnp.float32)],
        interpret=interpret,
    )(spikes, w_levels, scale, bias)
