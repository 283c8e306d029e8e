"""Pallas TPU kernel: fused LIF neuron over the spike-time axis.

The ASIC's LIF unit is a shift register (beta=0.5 right shift) + comparator
fed directly by crossbar partial sums (§IV-A-2).  The TPU analogue fuses
the whole T-step membrane recurrence into one kernel so the non-binary
membrane/current sequence never round-trips to HBM — the same
"no intermediate pre-activation storage" insight as the row-block-wise
mapping.

Grid tiles the flattened feature axis, laid out as 128-lane rows; each
program loops T steps in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _lif_kernel(cur_ref, out_ref, *, t_steps: int, beta: float, v_thresh: float):
    # loop-carried membrane: the fori_loop carry commits one f32 rounding
    # per step, exactly like the oracle's lax.scan — a static unroll would
    # let the backend evaluate the whole T-step mul/add chain at wider
    # precision and flip threshold-straddling comparators vs lif_ref (see
    # kernels/ref.py "Float-rounding discipline").  Blocks are
    # [T, rows, 128], so step t reads and writes whole [rows, 128] tiles at
    # a dynamic index on the untiled leading axis.
    def step(t, v):
        v = beta * v + cur_ref[t].astype(jnp.float32)
        spike = (v >= v_thresh).astype(jnp.float32)
        # the TPU has no f32 -> u8 conversion: go through int32
        out_ref[t] = spike.astype(jnp.int32).astype(out_ref.dtype)
        return v * (1.0 - spike)

    jax.lax.fori_loop(0, t_steps, step,
                      jnp.zeros(cur_ref.shape[1:], jnp.float32))


def lif_kernel(
    currents: Array,  # [T, R, 128] float (features in 128-lane rows)
    *,
    beta: float = 0.5,
    v_thresh: float = 1.0,
    block_rows: int = 256,
    interpret: bool,
) -> Array:
    t, r, lanes = currents.shape
    block_rows = min(block_rows, r)
    assert lanes == 128 and r % block_rows == 0, "ops.py pads the feature axis"
    kern = functools.partial(_lif_kernel, t_steps=t, beta=beta, v_thresh=v_thresh)
    spec = pl.BlockSpec((t, block_rows, lanes), lambda i: (0, i, 0))
    return pl.pallas_call(
        kern,
        grid=(r // block_rows,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((t, r, lanes), jnp.uint8),
        interpret=interpret,
    )(currents)
