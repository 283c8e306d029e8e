"""ShardedBackend: tensor-parallel spiking primitives under ``shard_map``.

The hardware analogue (paper §IV): Xpikeformer's throughput comes from
*spatial* parallelism — per-head SSA engine cores running concurrently and
AIMC crossbars tiled over output columns.  This module maps that onto the
``model`` axis of a ``(data, model)`` jax mesh:

* **column-parallel spiking linear** (``part="col"`` — Q/K/V projections,
  MLP in): crossbar *output columns* are sharded; the LIF membrane is
  per-column, so each shard quantises, accumulates and fires its own
  columns with zero communication.
* **row-parallel spiking linear** (``part="row"`` — attention out, MLP
  out): crossbar *input rows* are sharded; each shard accumulates its
  partial spike counts (shard-local programmed-AIMC matmul —
  ``kernels.ops.aimc_matmul_counts`` / ``kernels.ref.aimc_counts_ref``),
  the counts **psum** across ``model``, and scale/bias/LIF fire once on the
  reduced currents.  Counts are integer-valued f32, so the cross-shard
  reduction is *exact* and sharded == single-device bit-for-bit.
* **head-parallel SSA decode**: each shard runs the packed popcount tile
  over its own heads, drawing comparator integers from the per-``(seed,
  pos, head)`` streams (``draw_slot_decode_prns`` with the shard's global
  head offset ``lax.axis_index("model") * h_local``) — exactly the
  integers the single-device oracle draws for those heads.

Everything else (rate coding, embed/unembed, residual adds, cache
scatters) stays outside ``shard_map`` and is partitioned by GSPMD from the
parameter/state placements (``repro.distributed.executor``); batch/slot
dimensions ride the ``data`` axis.

Bit-exactness holds because every sharded reduction is over integer-valued
operands and every PRN stream is keyed by *logical* (slot, position, head)
coordinates, never by mesh coordinates.  Tensor parallelism engages for
the bit-exact digital substrates (``integer`` / ``pallas``); the
``reference`` backend's analog simulation (row-block ADC clipping, read
noise) is not decomposable across row shards, so it passes through and is
partitioned by GSPMD only.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.aimc_device import AIMCDeviceState
from repro.core import aimc as AM
from repro.kernels import ops as KOPS
from repro.kernels import ref as KREF
from repro.engine import _DecodeShims
from repro.kernels.plan import KVView
# single source of the jax.shard_map / jax.experimental shim
from repro.models.moe import _shard_map

Array = jax.Array

# which spiking-linear leaves are column- vs row-parallel (Megatron-style:
# the paper's per-head SSA cores and column-tiled crossbars)
TP_PARTS = {"wq": "col", "wk": "col", "wv": "col", "wi": "col", "wo": "row"}


@dataclasses.dataclass(frozen=True)
class TPPlan:
    """What the ``model`` axis can shard for a given config.

    Derived once from (cfg, mesh) and shared by parameter placement and
    the :class:`ShardedBackend`, so the two always agree on which leaves
    are sharded."""

    tp: int = 1  # model-axis size
    heads: bool = False  # h % tp == 0 and kv % tp == 0: SSA cores shardable

    @classmethod
    def from_config(cls, cfg, tp: int) -> "TPPlan":
        if tp <= 1:
            return cls()
        nh = getattr(cfg, "num_heads", 0) or 0
        kv = getattr(cfg, "num_kv_heads", 0) or 0
        return cls(tp=tp, heads=(nh > 0 and nh % tp == 0 and kv % tp == 0))

    def col_ok(self, d_out: int) -> bool:
        return self.tp > 1 and d_out % self.tp == 0

    def row_ok(self, d_in: int) -> bool:
        return self.tp > 1 and d_in % self.tp == 0


def _mat_dims(p: Any) -> Tuple[int, int]:
    """(d_in, d_out) of a normalised linear-param leaf."""
    if "hw" in p:
        hw = p["hw"]
        shape = hw.shape if isinstance(hw, AIMCDeviceState) else hw["levels"].shape
    else:
        shape = p["w"].shape
    return int(shape[-2]), int(shape[-1])


def _state_specs(col: bool, axis: str, lead: int = 0) -> AIMCDeviceState:
    """Per-field PartitionSpecs for a device state's crossbar matrix view.

    ``lead`` counts leading stack axes (0 for scan-sliced 2-D states inside
    shard_map, 1 for period-stacked leaves at placement time).  The single
    source of the AIMCDeviceState field -> spec mapping: parameter
    placement (``executor.param_pspecs_for_tree``) and the shard_map
    in_specs both derive from here, so they cannot disagree."""
    nl = (None,) * lead
    mat = P(*nl, None, axis) if col else P(*nl, axis, None)
    vec = P(*nl, axis) if col else P()
    sc = P()
    return AIMCDeviceState(levels=mat, eps=mat, nu=mat, scale=vec,
                           t_seconds=sc, gdc_gain=sc, levels_t=mat, img_inv=sc)


class ShardedBackend(_DecodeShims):
    """Tensor-parallel wrapper over a bit-exact engine backend.

    Implements the :class:`repro.engine.Backend` protocol; the mesh-aware
    entry points (``part=`` on ``spiking_linear``, ``spec.h0`` on
    ``decode_attention``) select the shard_map decomposition.  Two
    instances serve a mesh scheduler: the *decode* instance additionally
    shards the slot/batch dimension over ``data`` (``batch_axis="data"``);
    the *prefill* instance replicates it (prefill is batch-1).
    """

    differentiable = False

    def __init__(self, inner, mesh, cfg, *, batch_axis: Optional[str] = "data",
                 model_axis: str = "model"):
        from repro.parallel import sharding as SH

        sizes = SH.axis_sizes(mesh)
        self.inner = inner
        self.mesh = mesh
        self.cfg = cfg
        self.model_axis = model_axis if sizes.get(model_axis, 1) > 1 else None
        self.batch_axis = batch_axis if sizes.get(batch_axis or "", 1) > 1 else None
        self.data = sizes.get(batch_axis, 1) if self.batch_axis else 1
        # the analog reference path is not row-decomposable (per-row-block
        # ADC + read noise); TP engages for the digital substrates only
        if inner.name not in ("integer", "pallas"):
            self.model_axis = None
        self.plan = TPPlan.from_config(
            cfg, sizes.get(model_axis, 1) if self.model_axis else 1)
        self.name = f"sharded[{inner.name}]"
        self.bit_exact = inner.bit_exact
        # only offer the fused megakernel when the inner backend has it —
        # build_decode_plan keys "auto" off this being callable
        if not callable(getattr(inner, "decode_layer_fused", None)):
            self.decode_layer_fused = None

    # -- spec helpers ---------------------------------------------------

    def _batch(self, dim: int) -> Optional[str]:
        if self.batch_axis and dim % self.data == 0:
            return self.batch_axis
        return None

    def _x_spec(self, ndim: int, batch_dim: int, feat: Optional[str]) -> P:
        spec: list = [None] * ndim
        if ndim >= 3:  # [T, batch, ..., features]
            spec[1] = self._batch(batch_dim)
        if feat is not None:
            spec[-1] = feat
        return P(*spec)

    # -- passthrough primitives ----------------------------------------

    def ssa_attention(self, key, q, k, v, *, causal=False):
        return self.inner.ssa_attention(key, q, k, v, causal=causal)

    def lif(self, currents, *, beta=0.5, v_thresh=1.0):
        return self.inner.lif(currents, beta=beta, v_thresh=v_thresh)

    # -- head-parallel SSA decode --------------------------------------

    def decode_attention(self, view, q, spec, *, slot_keys):
        """Head-parallel SSA decode over a :class:`~repro.kernels.plan.
        KVView`: each shard runs the inner backend's decode over its own
        heads, drawing comparator integers from the per-``(seed, pos,
        global head)`` streams (``spec.h0`` plus the shard's
        ``lax.axis_index`` offset) — exactly the integers the single-device
        oracle draws for those heads.  A paged view's page axis is never
        sharded (pages are global); only the KV-head axis rides ``model``;
        slots ride ``data``."""
        h = q.shape[2]
        if self.model_axis is None or not self.plan.heads or h % self.plan.tp:
            return self.inner.decode_attention(view, q, spec,
                                               slot_keys=slot_keys)
        axis = self.model_axis
        h_local = h // self.plan.tp
        b = self._batch(q.shape[1])
        q_spec = P(None, b, axis, None, None)

        def off():
            return jnp.asarray(spec.h0) + lax.axis_index(axis) * h_local

        if view.paged:
            pool_spec = P(None, None, axis, None, None)  # [P,T,KV,page_len,d]

            def body(sk, qb, kb, vb, tb):
                sub = dataclasses.replace(spec, h0=off())
                return self.inner.decode_attention(
                    KVView.from_pool(kb, vb, tb), qb, sub, slot_keys=sk)

            return _shard_map(
                body, mesh=self.mesh,
                in_specs=(P(b), q_spec, pool_spec, pool_spec, P(b, None)),
                out_specs=q_spec,
            )(slot_keys, q, view.k, view.v, view.page_table)

        def body(sk, qb, kb, vb):  # dense k/v [T,B,H,L,d]: head axis shards
            sub = dataclasses.replace(spec, h0=off())
            return self.inner.decode_attention(
                KVView.dense(kb, vb), qb, sub, slot_keys=sk)

        return _shard_map(
            body, mesh=self.mesh,
            in_specs=(P(b), q_spec, q_spec, q_spec),
            out_specs=q_spec,
        )(slot_keys, q, view.k, view.v)

    # -- head-parallel fused decode layer ------------------------------

    def decode_layer_fused(self, slot_keys, s, view, pos, wq, wk, wv,
                           wo=None, wi=None, wo2=None, *, hd, h0=0,
                           write_pids=None, with_tail=True, with_mlp=True,
                           sim=None):
        """Head-parallel shard of the fused decode megakernel.

        The attention stage (projections + packed SSA) runs inside
        ``shard_map`` with ``with_tail=False``: each shard launches the
        inner backend's megakernel over its own ``h_local`` query heads
        (column-sliced ``wq``/``wk``/``wv``; per-column quantisation is
        shard-local-exact) at global head offset ``h0 + axis_index *
        h_local``, producing its slice of the attention spikes and its own
        KV heads' new trains.  The FFN tail then rides the existing
        row/col-parallel spiking linears *outside* the shard_map — the
        row path psums integer spike counts and fires LIF once, which is
        bit-identical to the fused kernel's tail (same committed
        roundings), so sharded-fused == single-device-fused exactly."""
        from repro import engine as E

        kvh = view.k.shape[2] if view.paged else view.k.shape[3]
        tp_ok = (self.model_axis is not None and self.plan.heads
                 and kvh % self.plan.tp == 0)
        if not tp_ok:
            return self.inner.decode_layer_fused(
                slot_keys, s, view, pos, wq, wk, wv, wo, wi, wo2, hd=hd,
                h0=h0, write_pids=write_pids, with_tail=with_tail,
                with_mlp=with_mlp, sim=sim)
        axis = self.model_axis
        # normalise the projection leaves so operands and specs agree on
        # the pytree shape (shard_map in_specs must mirror the operands)
        pq, pk, pv = (E._linear_parts(w) for w in (wq, wk, wv))
        h = _mat_dims(pq)[1] // hd
        h_local = h // self.plan.tp
        b = self._batch(s.shape[1])
        if view.paged:
            kv_spec = P(None, None, axis, None, None)  # [P,T,KV,page_len,hd]
            view_spec = KVView.from_pool(kv_spec, kv_spec, P(b, None))
        else:
            kv_spec = P(b, None, None, axis, None)  # [B,T,L,KV,hd]
            view_spec = KVView.dense(kv_spec, kv_spec)
        wp_specs = (P(b),) if write_pids is not None else ()
        wp_args = (write_pids,) if write_pids is not None else ()

        def body(sk, sb, vw, ps, wq_, wk_, wv_, *rest):
            off = jnp.asarray(h0) + lax.axis_index(axis) * h_local
            return self.inner.decode_layer_fused(
                sk, sb, vw, ps, wq_, wk_, wv_, hd=hd, h0=off,
                write_pids=rest[0] if rest else None,
                with_tail=False, sim=sim)

        a, k_new, v_new = _shard_map(
            body, mesh=self.mesh,
            in_specs=(P(b), P(None, b, None), view_spec, P(b),
                      self._p_specs(pq, col=True), self._p_specs(pk, col=True),
                      self._p_specs(pv, col=True)) + wp_specs,
            out_specs=(P(None, b, axis), P(None, b, axis, None),
                       P(None, b, axis, None)),
        )(slot_keys, s, view, pos, pq, pk, pv, *wp_args)
        if not with_tail:
            return a, k_new, v_new
        s1 = s + self.spiking_linear(None, wo, a, sim, part="row")
        if with_mlp:
            h1 = self.spiking_linear(None, wi, s1, sim, part="col")
            s1 = s1 + self.spiking_linear(None, wo2, h1, sim, part="row")
        return s1, k_new, v_new

    # -- tensor-parallel spiking linear --------------------------------

    def spiking_linear(self, key, p, spikes, sim=None, *, part=None):
        from repro import engine as E

        pn = E._linear_parts(p)
        d_in, d_out = _mat_dims(pn)
        active = (self.model_axis is not None and (
            (part == "col" and self.plan.col_ok(d_out))
            or (part == "row" and self.plan.row_ok(d_in))))
        if not active:
            return self.inner.spiking_linear(key, p, spikes, sim, part=part)
        if part == "col":
            return self._col_linear(key, pn, spikes, sim)
        return self._row_linear(key, pn, spikes, sim)

    def _p_specs(self, p, col: bool):
        axis = self.model_axis
        mat = P(None, axis) if col else P(axis, None)
        vec = P(axis) if col else P()
        specs = {}
        if "w" in p:
            specs["w"] = mat
        if "hw" in p:
            hw = p["hw"]
            specs["hw"] = (_state_specs(col, axis)
                           if isinstance(hw, AIMCDeviceState)
                           else {"levels": mat, "scale": vec})
        specs["b"] = vec if p.get("b") is not None else None
        return specs

    def _col_linear(self, key, p, spikes, sim):
        """Output columns sharded: each shard fires its own LIF columns."""
        x_in = self._x_spec(spikes.ndim, spikes.shape[1], None)
        x_out = self._x_spec(spikes.ndim, spikes.shape[1], self.model_axis)
        inner, p_specs = self.inner, self._p_specs(p, col=True)

        if key is None:
            def body(pl_, sp_):
                return inner.spiking_linear(None, pl_, sp_, sim)

            return _shard_map(body, mesh=self.mesh,
                              in_specs=(p_specs, x_in),
                              out_specs=x_out)(p, spikes)

        def body(k_, pl_, sp_):
            return inner.spiking_linear(k_, pl_, sp_, sim)

        return _shard_map(body, mesh=self.mesh,
                          in_specs=(P(), p_specs, x_in),
                          out_specs=x_out)(key, p, spikes)

    def _row_linear(self, key, p, spikes, sim):
        """Input rows sharded: psum integer spike counts, fire LIF once.

        The cross-shard reduction runs on integer-valued f32 partial counts
        (exact), then scale/bias/LIF replay the fused kernel's op sequence
        on the reduced currents — bit-identical to the single-device fused
        ``aimc_spiking_linear``."""
        from repro import engine as E

        axis = self.model_axis
        acfg = (sim or E._IDEAL_SIM).cfg
        inner = self.inner
        use_kernel = isinstance(inner, E.PallasBackend)
        x_in = self._x_spec(spikes.ndim, spikes.shape[1], axis)
        x_out = self._x_spec(spikes.ndim, spikes.shape[1], None)

        def body(pl_, sp_):
            flat, unflatten = E._flatten_time(sp_)
            flat = flat.astype(jnp.float32)
            if "hw" in pl_:
                hw = pl_["hw"]
                if isinstance(hw, AIMCDeviceState):
                    levels, scale = hw.levels_t, hw.eff_scale
                else:
                    levels, scale = hw["levels"].astype(jnp.int8), hw["scale"]
            else:
                # per-column scale needs the *global* column max: pmax is
                # order-invariant, so shard-local quantisation with the
                # pmax'd scale reproduces the single-device levels exactly
                amax = lax.pmax(jnp.max(jnp.abs(pl_["w"]), axis=-2), axis)
                scale = jnp.where(amax > 0, amax / acfg.levels, 1.0
                                  ).astype(jnp.float32)
                levels = AM.quantize_levels(pl_["w"], scale, acfg
                                            ).astype(jnp.int8)
            if use_kernel:
                counts = KOPS.aimc_matmul_counts(flat, levels,
                                                 interpret=inner.interpret)
            else:
                counts = KREF.aimc_counts_ref(flat, levels)
            counts = lax.psum(counts, axis)  # exact: integer-valued f32
            pre = counts * scale[None, None, :]
            if pl_.get("b") is not None:
                pre = pre + pl_["b"].astype(jnp.float32)[None, None, :]
            return unflatten(inner.lif(pre))

        return _shard_map(body, mesh=self.mesh,
                          in_specs=(self._p_specs(p, col=False), x_in),
                          out_specs=x_out)(p, spikes)
