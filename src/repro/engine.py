"""Unified Xpikeformer engine: one model API, pluggable compute backends.

The paper's central claim is that a *single* spiking transformer runs on
interchangeable substrates — GPU float math for training, a bit-faithful
integer simulation of the SSA/AIMC hardware, and the accelerated engine
itself.  This module makes that a first-class API instead of three
disconnected code paths:

* :class:`Backend` — the protocol every substrate implements: the three
  spiking primitive ops the models are built from (``ssa_attention``,
  ``lif``, ``spiking_linear``).
* ``"reference"``  — differentiable float ops + straight-through Bernoulli
  samplers; the only backend that models the AIMC *analog* non-idealities
  (HWAT noise, PCM drift, read noise, GDC).  Use for training and for the
  paper's drift/BER studies.
* ``"integer"``    — bit-faithful integer simulation of the digital SSA
  engine + 5-bit quantised crossbars (the hardware oracle).  Deterministic
  given a key; not differentiable.
* ``"pallas"``     — the bit-packed Pallas TPU kernels (popcount SSA,
  fused-membrane LIF, fused crossbar-MVM + LIF), with packing and padding
  absorbed inside the backend.  Bit-exact against ``"integer"`` given the
  same key.
* :class:`XpikeformerEngine` — facade over the paper models (spiking ViT
  and spiking GPT): ``from_config`` / ``init`` / ``forward`` / ``program``
  (PCM programming) / ``classify`` / ``detect_symbols``.

Quick start::

    from repro.engine import XpikeformerEngine
    eng = XpikeformerEngine.from_config("xpikeformer-vit-smoke", backend="pallas")
    params = eng.init(jax.random.PRNGKey(0))
    logits = eng.forward(images, jax.random.PRNGKey(1))

Serving (generic LM-stack archs, ``task="lm"``)::

    eng = XpikeformerEngine.from_config("xpikeformer-gpt-4-256", task="lm",
                                        backend="pallas")
    eng.init(jax.random.PRNGKey(0))
    outs = eng.generate([[5, 7, 9], [3, 1]], max_new=16)       # batch API
    outs, stats = eng.serve(prompts, max_new=16, slots=8)      # continuous batching
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Protocol, Union, runtime_checkable

import jax
import jax.numpy as jnp

from repro import aimc_device as AD
from repro.aimc_device import AIMCDeviceState
from repro.core import aimc as AM
from repro.core import spikes as SP
from repro.core import ssa as SSA
from repro.core import spiking_transformer as ST
from repro.core.spiking_transformer import AIMCSim, SpikingConfig
from repro.kernels import decode_fused as KFD
from repro.kernels import ops as KOPS
from repro.kernels import ref as KREF
from repro.kernels.plan import AttnSpec, KVView
from repro.kernels.platform import interpret_mode

Array = jax.Array

_IDEAL_SIM = AIMCSim()  # wmode="ideal": plain float weights


# ---------------------------------------------------------------------------
# Backend protocol
# ---------------------------------------------------------------------------


@runtime_checkable
class Backend(Protocol):
    """A compute substrate for the three spiking primitives.

    ``p`` in :meth:`spiking_linear` is a linear-layer param leaf in any of
    the model formats: ``{"w", "b"}`` (float weights), ``{"hw", "b"}``
    (programmed PCM state from :func:`repro.core.spiking_transformer.
    program_model`), or a bare weight array.
    """

    name: str
    differentiable: bool
    bit_exact: bool

    def ssa_attention(self, key: Array, q: Array, k: Array, v: Array, *,
                      causal: bool = False) -> Array:
        """Stochastic spiking attention over ``[T,B,H,N,d]`` spike trains."""
        ...

    def decode_attention(self, view: KVView, q: Array, spec: AttnSpec, *,
                         slot_keys: Array) -> Array:
        """One-query SSA decode against cached KV spike trains (serving).

        The single decode surface: ``view`` is the K/V storage union —
        dense slot caches (``k``/``v [T,B,H,L,d]``, zero beyond each
        slot's position; zero spikes never beat a comparator draw, so
        validity masking is implicit) or a block-paged pool
        (``k``/``v [P,T,KV,page_len,d]`` plus ``page_table [B,MP]``;
        entry 0 is the permanently-zero null page, and GQA repeat happens
        inside the backend).  ``q [T,B,H,1,d]`` is the token being
        decoded.  ``spec`` carries the static geometry: ``i_max`` is the
        output comparator range — the *logical* cache capacity (the
        hardware tile dimension), fixed regardless of fill level and
        layout, so dense and paged decode draw identical streams;
        ``spec.h0`` is the mesh-aware entry point — a tensor-parallel
        shard that owns heads ``[h0, h0+H)`` passes its global head
        offset (possibly traced) and draws exactly the single-device
        oracle's integers for those heads (see
        :class:`repro.distributed.ShardedBackend`).

        ``slot_keys [B,2]`` are per-slot uint32 PRNG keys: every slot
        draws its own comparator integers so continuous-batching
        admission cannot perturb running slots; within a slot every head
        draws from ``f(seed, pos, global head index)``.

        The pre-PR-7 ``ssa_attention_decode`` / ``ssa_attention_decode_
        paged`` methods survive as deprecation shims (bit-exact
        forwarders) on every bundled backend."""
        ...

    def lif(self, currents: Array, *, beta: float = 0.5,
            v_thresh: float = 1.0) -> Array:
        """LIF neuron over a ``[T, ...]`` current sequence."""
        ...

    def spiking_linear(self, key: Optional[Array], p: Any, spikes: Array,
                       sim: Optional[AIMCSim] = None, *,
                       part: Optional[str] = None) -> Array:
        """``LIF(W s^t + b)`` over a ``[T, ..., d_in]`` spike train.

        ``part`` is a mesh-aware tensor-parallel hint from the model code:
        ``"col"`` for output-column-sharded layers (Q/K/V projections, MLP
        in) and ``"row"`` for input-sharded layers whose partial spike
        counts must psum before the LIF fires (attention out, MLP out).
        Single-device backends ignore it; ``repro.distributed.
        ShardedBackend`` uses it to pick the shard_map decomposition."""
        ...


def _linear_parts(p: Any) -> Dict[str, Any]:
    """Normalise a linear param leaf to ``{"w"|"hw", "b"}`` form."""
    if isinstance(p, AIMCDeviceState):
        return {"hw": p, "b": None}
    if isinstance(p, dict):
        return p
    return {"w": p, "b": None}


def _levels_scale(p: Dict[str, Any], sim: AIMCSim):
    """Integer conductance levels + per-column scale for a linear leaf.

    Programmed PCM state (:class:`repro.aimc_device.AIMCDeviceState`)
    executes its *digital image* — the drifted, GDC-compensated int8
    ``levels_t`` and per-column ``eff_scale`` that ``drift_to`` /
    ``recalibrate`` folded at calibration time, so the hot loop stays an
    int8 MXU matmul on every backend.  Float weights are quantised on the
    fly via the single source of truth
    (:func:`repro.aimc_device.quantize_weights`); legacy ``{"hw": {...}}``
    dicts keep their ideal-levels behaviour.  Continuous analog
    non-idealities (read noise, per-device drift residuals, shared-ADC
    clipping) remain reference-backend-only.
    """
    if "hw" in p:
        hw = p["hw"]
        if isinstance(hw, AIMCDeviceState):
            return hw.levels_t, hw.eff_scale
        return hw["levels"].astype(jnp.int8), hw["scale"]
    levels, scale = AD.quantize_weights(p["w"], sim.cfg)
    return levels.astype(jnp.int8), scale


def _gather_paged_kv(q: Array, kpool: Array, vpool: Array, page_table: Array):
    """Dense [T,B,H,L,d] K/V views of a paged pool (GQA-repeated to match q).

    The non-kernel backends' paged-decode path: gather each slot's pages
    through its table (null pages read as zeros) and hand the dense view to
    the slot-dense decode — bit-identical content, identical PRN streams."""
    h, kv = q.shape[2], kpool.shape[2]
    k = KOPS.gather_kv_pages(kpool, page_table)  # [T, B, KV, L, d]
    v = KOPS.gather_kv_pages(vpool, page_table)
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _flatten_time(spikes: Array):
    """[T, *lead, d_in] -> ([T, M, d_in], unflatten)"""
    t = spikes.shape[0]
    lead = spikes.shape[1:-1]
    d_in = spikes.shape[-1]
    flat = spikes.reshape(t, -1, d_in)

    def unflatten(out: Array) -> Array:
        return out.reshape((t,) + lead + (out.shape[-1],))

    return flat, unflatten


def _w_triple(p: Any, sim: AIMCSim):
    """Linear param leaf -> the fused kernels' (levels, scale, bias) triple."""
    parts = _linear_parts(p)
    levels, scale = _levels_scale(parts, sim)
    return (levels, scale, parts.get("b"))


class _DecodeShims:
    """The pre-PR-7 decode surface, forwarding to :meth:`decode_attention`.

    ``ssa_attention_decode`` / ``ssa_attention_decode_paged`` and their
    ``i_max``/``h0``/pool-vs-dense positional soup are deprecated: the one
    decode surface is ``decode_attention(view, q, spec)``.  These shims
    forward bit-exactly (asserted by the test suite) and warn once per
    trace site."""

    def ssa_attention_decode(self, slot_keys, q, k, v, *, i_max, h0=0):
        warnings.warn(
            "Backend.ssa_attention_decode is deprecated; use "
            "decode_attention(KVView.dense(k, v), q, AttnSpec(i_max, h0))",
            DeprecationWarning, stacklevel=2)
        return self.decode_attention(
            KVView.dense(k, v), q, AttnSpec(i_max=i_max, h0=h0),
            slot_keys=slot_keys)

    def ssa_attention_decode_paged(self, slot_keys, q, kpool, vpool,
                                   page_table, *, i_max, h0=0):
        warnings.warn(
            "Backend.ssa_attention_decode_paged is deprecated; use "
            "decode_attention(KVView.from_pool(kpool, vpool, page_table), "
            "q, AttnSpec(i_max, h0, groups))",
            DeprecationWarning, stacklevel=2)
        return self.decode_attention(
            KVView.from_pool(kpool, vpool, page_table), q,
            AttnSpec(i_max=i_max, h0=h0,
                     groups=q.shape[2] // kpool.shape[2]),
            slot_keys=slot_keys)


# ---------------------------------------------------------------------------
# Reference backend — differentiable float path (training)
# ---------------------------------------------------------------------------


class ReferenceBackend(_DecodeShims):
    """Float ops + straight-through Bernoulli/Heaviside surrogates.

    The only backend usable under ``jax.grad``; also the only one that
    applies the AIMC *analog* simulation (wmode="hwat"/"hw": programming
    noise, read noise, drift, GDC)."""

    name = "reference"
    differentiable = True
    bit_exact = False

    def ssa_attention(self, key, q, k, v, *, causal=False):
        return SSA.ssa_attention(key, q, k, v, causal=causal)

    def decode_attention(self, view, q, spec, *, slot_keys):
        if view.paged:
            k, v = _gather_paged_kv(q, view.k, view.v, view.page_table)
        else:
            k, v = view.k, view.v
        i_max, h0 = spec.i_max, spec.h0
        d = q.shape[-1]
        heads = jnp.asarray(h0) + jnp.arange(q.shape[2])

        def per_slot(key, qb, kb, vb):  # [T,H,1,d] x [T,H,L,d]
            def per_head(hi, qh, kh, vh):  # [T,1,d] x [T,L,d]
                k1, k2 = jax.random.split(jax.random.fold_in(key, hi))
                qf, kf, vf = (t.astype(jnp.float32) for t in (qh, kh, vh))
                counts_s = jnp.einsum("tnd,tld->tnl", qf, kf)
                p_s = counts_s / d
                s = SP.bernoulli_st(p_s, jax.random.uniform(k1, p_s.shape))
                counts_a = jnp.einsum("tnl,tld->tnd", s, vf)
                p_a = jnp.clip(counts_a / float(i_max), 0.0, 1.0)
                return SP.bernoulli_st(p_a, jax.random.uniform(k2, p_a.shape))

            # per-(slot, head) streams: f(seed, pos, global head) — the same
            # convention as the integer/pallas backends, so head-sharded
            # decode draws shard-locally without perturbing any stream
            return jax.vmap(per_head, in_axes=(0, 1, 1, 1), out_axes=1)(
                heads, qb, kb, vb)

        return jax.vmap(per_slot, in_axes=(0, 1, 1, 1), out_axes=1)(
            slot_keys, q, k, v
        )

    def lif(self, currents, *, beta=0.5, v_thresh=1.0):
        return SP.lif(currents, SP.LIFParams(beta=beta, v_thresh=v_thresh))

    def spiking_linear(self, key, p, spikes, sim=None, *, part=None):
        sim = sim or _IDEAL_SIM
        p = _linear_parts(p)
        if isinstance(p.get("hw"), AIMCDeviceState):
            # device-state lifecycle: per-device drift at the state's own
            # t_seconds, read noise, shared ADC, *stored* (stale) GDC gain
            pre = jax.vmap(
                lambda zt: AD.analog_matmul(key, zt, p["hw"], sim.cfg)
            )(spikes)
        elif "hw" in p:  # legacy dict state: full analog crossbar sim
            pre = jax.vmap(
                lambda zt: AM.aimc_matmul(
                    key, zt, p["hw"], sim.cfg, t_seconds=sim.t_seconds, gdc=sim.gdc
                )
            )(spikes)
        else:
            w = p["w"]
            if sim.wmode == "hwat":
                assert key is not None
                w = AM.hwat_weights(key, w, sim.cfg)
            pre = jax.vmap(lambda zt: zt @ w)(spikes)
        if p.get("b") is not None:
            pre = pre + p["b"]
        return SP.lif(pre)


# ---------------------------------------------------------------------------
# Integer backend — bit-faithful hardware oracle
# ---------------------------------------------------------------------------


class IntegerBackend(_DecodeShims):
    """Bit-faithful integer simulation of the SSA engine's digital datapath.

    Draws the comparator PRNs with the exact convention the pallas backend
    uses (:func:`repro.kernels.ops.draw_comparator_prns`), so the two are
    bit-identical given the same key — this backend is the correctness
    contract the kernels are validated against (including the fused
    decode-layer oracle, :meth:`decode_layer_fused`)."""

    name = "integer"
    differentiable = False
    bit_exact = True

    def ssa_attention(self, key, q, k, v, *, causal=False):
        t, b, h, n, d = q.shape
        g = t * b * h
        rs, ra = KOPS.draw_comparator_prns(key, (g, n, n), (g, n, d), d, n)
        out = KREF.ssa_attention_ref(
            q.reshape(g, n, d), k.reshape(g, n, d), v.reshape(g, n, d),
            rs, ra, causal=causal,
        )
        return out.reshape(t, b, h, n, d)

    def decode_attention(self, view, q, spec, *, slot_keys):
        t, b, h, n1, d = q.shape
        if view.paged:
            l = view.page_table.shape[1] * view.k.shape[3]
            # identical streams to the dense layout (bit-exact across modes)
            rs, ra = KOPS.draw_slot_decode_prns(slot_keys, t, h, l, d,
                                                spec.i_max, spec.h0)
            out = KREF.ssa_decode_paged_ref(
                jnp.moveaxis(q, 1, 0), view.k, view.v, view.page_table,
                rs.reshape(b, t, h, 1, l), ra.reshape(b, t, h, 1, d),
            )
            return jnp.moveaxis(out, 0, 1)
        k, v = view.k, view.v
        l = k.shape[3]
        # same per-(slot, head) PRN convention as the pallas wrapper
        # (bit-exactness); spec.h0 offsets the head streams for TP shards
        rs, ra = KOPS.draw_slot_decode_prns(slot_keys, t, h, l, d,
                                            spec.i_max, spec.h0)
        g = b * t * h
        out = KREF.ssa_decode_ref(
            jnp.moveaxis(q, 1, 0).reshape(g, 1, d),
            jnp.moveaxis(k, 1, 0).reshape(g, l, d),
            jnp.moveaxis(v, 1, 0).reshape(g, l, d),
            rs.reshape(g, 1, l), ra.reshape(g, 1, d),
        )
        return jnp.moveaxis(out.reshape(b, t, h, 1, d), 0, 1)

    def decode_layer_fused(self, slot_keys, s, view, pos, wq, wk, wv,
                           wo=None, wi=None, wo2=None, *, hd, h0=0,
                           write_pids=None, with_tail=True, with_mlp=True,
                           sim=None):
        """Fused-layer oracle: one decoder layer step, composed from the
        per-primitive reference oracles (see
        :func:`repro.kernels.ref.decode_layer_ref`).  The contract the
        pallas megakernel is fuzzed against; integer-fused ==
        integer-unfused by construction."""
        sim = sim or _IDEAL_SIM

        def tri(w):
            return None if w is None else _w_triple(w, sim)

        t, b, _ = s.shape
        wq = tri(wq)
        h = wq[0].shape[1] // hd
        if view.paged:
            l = view.page_table.shape[1] * view.k.shape[3]
            rs4, ra4 = KFD.draw_layer_prns(slot_keys, t, h, l, hd, h0)
            return KREF.decode_layer_paged_ref(
                s, view.k, view.v, view.page_table, pos, write_pids,
                wq, tri(wk), tri(wv), tri(wo), tri(wi), tri(wo2), rs4, ra4,
                hd=hd, with_tail=with_tail, with_mlp=with_mlp)
        l = view.k.shape[2]
        rs4, ra4 = KFD.draw_layer_prns(slot_keys, t, h, l, hd, h0)
        return KREF.decode_layer_ref(
            s, view.k, view.v, pos, wq, tri(wk), tri(wv), tri(wo), tri(wi),
            tri(wo2), rs4, ra4, hd=hd, with_tail=with_tail,
            with_mlp=with_mlp)

    def lif(self, currents, *, beta=0.5, v_thresh=1.0):
        t = currents.shape[0]
        flat = currents.astype(jnp.float32).reshape(t, -1)
        out = KREF.lif_ref(flat, beta=beta, v_thresh=v_thresh)
        return out.reshape(currents.shape)

    def spiking_linear(self, key, p, spikes, sim=None, *, part=None):
        sim = sim or _IDEAL_SIM
        p = _linear_parts(p)
        levels, scale = _levels_scale(p, sim)
        flat, unflatten = _flatten_time(spikes)
        out = KREF.aimc_spiking_linear_ref(
            flat.astype(jnp.float32), levels, scale, p.get("b")
        )
        return unflatten(out)


# ---------------------------------------------------------------------------
# Pallas backend — bit-packed TPU kernels on the model hot path
# ---------------------------------------------------------------------------


class PallasBackend(_DecodeShims):
    """The accelerated engine: popcount SSA + fused LIF/crossbar kernels.

    ``interpret`` is decided by the platform
    (:func:`repro.kernels.platform.interpret_mode`): the kernel bodies run
    through the Pallas interpreter on CPU (exact semantics) and compile to
    Mosaic on TPU; any other platform raises.  Packing to uint32 lanes
    and padding to kernel block multiples happen inside the backend —
    callers hand over plain ``[T,B,H,N,d]`` / ``[T,...,d_in]`` spike
    trains."""

    name = "pallas"
    differentiable = False
    bit_exact = True

    def __init__(self):
        self.interpret = interpret_mode()

    def ssa_attention(self, key, q, k, v, *, causal=False):
        return KOPS.ssa_attention_packed(
            q, k, v, key, causal=causal, interpret=self.interpret
        )

    def decode_attention(self, view, q, spec, *, slot_keys):
        if view.paged:
            return KOPS.ssa_attention_decode_paged_packed(
                q, view.k, view.v, view.page_table, slot_keys, spec.h0,
                i_max=spec.i_max, interpret=self.interpret,
            )
        return KOPS.ssa_attention_decode_packed(
            q, view.k, view.v, slot_keys, spec.h0, i_max=spec.i_max,
            interpret=self.interpret,
        )

    def decode_layer_fused(self, slot_keys, s, view, pos, wq, wk, wv,
                           wo=None, wi=None, wo2=None, *, hd, h0=0,
                           write_pids=None, with_tail=True, with_mlp=True,
                           sim=None):
        """One megakernel launch per decoder layer step (the PR-7 tentpole):
        packed-VMEM SSA + fused projections/FFN, dense or paged per the
        view; bit-exact vs :meth:`IntegerBackend.decode_layer_fused`."""
        sim = sim or _IDEAL_SIM

        def tri(w):
            return None if w is None else _w_triple(w, sim)

        if view.paged:
            return KFD.fused_decode_layer_paged(
                slot_keys, s, view.k, view.v, view.page_table, pos,
                write_pids, tri(wq), tri(wk), tri(wv), tri(wo), tri(wi),
                tri(wo2), h0, hd=hd, with_tail=with_tail, with_mlp=with_mlp,
                interpret=self.interpret)
        return KFD.fused_decode_layer(
            slot_keys, s, view.k, view.v, pos, tri(wq), tri(wk), tri(wv),
            tri(wo), tri(wi), tri(wo2), h0, hd=hd, with_tail=with_tail,
            with_mlp=with_mlp, interpret=self.interpret)

    def lif(self, currents, *, beta=0.5, v_thresh=1.0):
        return KOPS.lif_fused(
            currents.astype(jnp.float32), beta=beta, v_thresh=v_thresh,
            interpret=self.interpret,
        )

    def spiking_linear(self, key, p, spikes, sim=None, *, part=None):
        sim = sim or _IDEAL_SIM
        p = _linear_parts(p)
        levels, scale = _levels_scale(p, sim)
        flat, unflatten = _flatten_time(spikes)
        out = KOPS.aimc_spiking_linear(
            flat.astype(jnp.float32), levels, scale, p.get("b"),
            interpret=self.interpret,
        )
        return unflatten(out)


# ---------------------------------------------------------------------------
# Metering backend — spike counts x Table-II op energies (eager only)
# ---------------------------------------------------------------------------


class MeteringBackend(_DecodeShims):
    """Wraps any backend and meters energy from **measured** spike counts.

    Every primitive call records its operand/output spike events and
    converts them to picojoules with the Table-II op energies
    (``repro.energy.model.meter_*``), accumulating into :attr:`report`.
    Counting forces a host sync per call, so metering is for *eager*
    forwards — ``engine.forward(..., metering=True)`` — not for jitted
    serving loops (those meter through the decode-step activity counters,
    see ``repro.serving.scheduler``)."""

    def __init__(self, inner: Backend):
        from repro.energy import model as EM

        self.inner = inner
        self.report = EM.EnergyReport()
        self.name = f"metered[{inner.name}]"
        self.differentiable = inner.differentiable
        self.bit_exact = inner.bit_exact

    @staticmethod
    def _count(x) -> float:
        return float(jnp.sum(jnp.asarray(x, jnp.float32)))

    def ssa_attention(self, key, q, k, v, *, causal=False):
        from repro.energy import model as EM

        out = self.inner.ssa_attention(key, q, k, v, causal=causal)
        t, b, h, n, d = q.shape
        qs, ks, vs = self._count(q), self._count(k), self._count(v)
        e = EM.meter_ssa(t, b * h, n, n, d, qs / q.size, ks / k.size,
                         vs / v.size)
        self.report.ssa_pj += e["ssa"]
        self.report.spikes_in += qs + ks + vs
        self.report.spikes_out += self._count(out)
        self.report.calls += 1
        return out

    def decode_attention(self, view, q, spec, *, slot_keys):
        from repro.energy import model as EM

        out = self.inner.decode_attention(view, q, spec, slot_keys=slot_keys)
        t, b, h, n, d = q.shape
        if view.paged:
            kpool, vpool, page_table = view.k, view.v, view.page_table
            mp, kv = page_table.shape[1], kpool.shape[2]
            l = mp * kpool.shape[3]
            rep = h // kv
            # meter the *logical* gathered K/V the tile streams, without
            # ever materialising it: per-page spike totals indexed through
            # the page table give the gathered count at O(pool) cost, and
            # the GQA repeat is a plain multiplier on count and size alike
            kc = jnp.sum(kpool.astype(jnp.float32), axis=(1, 2, 3, 4))  # [P]
            vc = jnp.sum(vpool.astype(jnp.float32), axis=(1, 2, 3, 4))
            qs = self._count(q)
            ks = rep * float(jnp.sum(kc[page_table]))
            vs = rep * float(jnp.sum(vc[page_table]))
            kv_size = b * t * rep * kv * l * d  # the dense gathered view
        else:
            l = view.k.shape[3]
            qs, ks, vs = (self._count(q), self._count(view.k),
                          self._count(view.v))
            kv_size = view.k.size
        e = EM.meter_ssa(t, b * h, n, l, d, qs / q.size, ks / kv_size,
                         vs / kv_size)
        self.report.ssa_pj += e["ssa"]
        self.report.spikes_in += qs + ks + vs
        self.report.spikes_out += self._count(out)
        self.report.calls += 1
        return out

    def lif(self, currents, *, beta=0.5, v_thresh=1.0):
        from repro.energy import constants as C

        out = self.inner.lif(currents, beta=beta, v_thresh=v_thresh)
        self.report.lif_pj += currents.size * C.E_LIF_STEP
        self.report.spikes_out += self._count(out)
        self.report.calls += 1
        return out

    def spiking_linear(self, key, p, spikes, sim=None, *, part=None):
        from repro.energy import model as EM

        out = self.inner.spiking_linear(key, p, spikes, sim, part=part)
        t = spikes.shape[0]
        d_in, d_out = spikes.shape[-1], out.shape[-1]
        tokens = int(spikes.size // (t * d_in))
        ins = self._count(spikes)
        e = EM.meter_spiking_linear(t, tokens, d_in, d_out, ins)
        self.report.aimc_pj += e["aimc"]
        self.report.lif_pj += e["lif"]
        self.report.spikes_in += ins
        self.report.spikes_out += self._count(out)
        self.report.calls += 1
        return out


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

BACKENDS = {
    "reference": ReferenceBackend,
    "integer": IntegerBackend,
    "pallas": PallasBackend,
}


def register_backend(name: str, factory) -> None:
    """Register a custom backend factory under ``name``."""
    BACKENDS[name] = factory


def get_backend(spec: Union[str, Backend, None], **kwargs) -> Backend:
    """Resolve a backend name (or pass an instance through)."""
    if spec is None:
        return ReferenceBackend()
    if isinstance(spec, str):
        if spec not in BACKENDS:
            raise KeyError(f"unknown backend {spec!r}; known: {sorted(BACKENDS)}")
        return BACKENDS[spec](**kwargs)
    return spec


# ---------------------------------------------------------------------------
# Engine facade
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class XpikeformerEngine:
    """One handle over the paper's models across all compute substrates.

    Built via :meth:`from_config` from a registered arch name (see
    ``repro.configs.xpikeformer.SPIKING_ARCHS``) or a raw
    :class:`SpikingConfig`.  Holds the model params after :meth:`init` /
    :meth:`program` so task-level helpers (:meth:`classify`,
    :meth:`detect_symbols`) are one-liners, but every method also accepts
    explicit ``params`` for functional use.
    """

    cfg: Any  # SpikingConfig (paper models) or ModelConfig (task="lm")
    task: str  # "vit" | "gpt" | "lm"
    backend: Backend
    sim: AIMCSim
    params: Any = None
    # schedulers are cached per (slots, cache_len, moe_impl): their jitted
    # decode/prefill closures are multi-second compiles worth keeping warm
    _schedulers: Dict[Any, Any] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    # -- construction --------------------------------------------------

    @classmethod
    def from_config(
        cls,
        name_or_cfg: Union[str, SpikingConfig, Any],
        *,
        task: Optional[str] = None,
        backend: Union[str, Backend] = "reference",
        wmode: str = "ideal",
        aimc_cfg: Optional[AM.AIMCConfig] = None,
        t_seconds: float = 0.0,
        gdc: bool = True,
        reduced: bool = False,
        **backend_kwargs,
    ) -> "XpikeformerEngine":
        """Build an engine from an arch name or config.

        Names resolve against the paper models first
        (``configs.xpikeformer.SPIKING_ARCHS`` — spiking ViT / GPT), then
        against the generic LM-stack registry (``configs.registry`` —
        ``task="lm"``, served via :meth:`generate` / :meth:`serve`);
        ``reduced=True`` picks the registry arch's CPU smoke reduction.
        A raw :class:`SpikingConfig` or ``ModelConfig`` is accepted too.
        """
        from repro.configs.base import ModelConfig

        if isinstance(name_or_cfg, str):
            from repro.configs.xpikeformer import SPIKING_ARCHS
            from repro.configs.registry import ARCHS, get_config, reduced_config

            # "xpikeformer-gpt-*" names exist both as paper models and as
            # LM-stack registry archs; task="lm" forces the registry.
            if name_or_cfg in SPIKING_ARCHS and task != "lm":
                task_, cfg = SPIKING_ARCHS[name_or_cfg]
                task = task or task_
            elif name_or_cfg in ARCHS:
                cfg = reduced_config(name_or_cfg) if reduced else get_config(name_or_cfg)
                task = task or "lm"
            else:
                raise KeyError(
                    f"unknown engine arch {name_or_cfg!r}; known: "
                    f"{sorted(SPIKING_ARCHS)} + registry {sorted(ARCHS)}"
                )
        elif isinstance(name_or_cfg, ModelConfig):
            cfg = name_or_cfg
            task = task or "lm"
        else:
            cfg = name_or_cfg
            if task is None:
                task = "gpt" if cfg.input_dim > 0 else "vit"
        sim = AIMCSim(
            wmode=wmode, cfg=aimc_cfg or AM.AIMCConfig(),
            t_seconds=t_seconds, gdc=gdc,
        )
        return cls(cfg=cfg, task=task, backend=get_backend(backend, **backend_kwargs),
                   sim=sim)

    # -- lifecycle -----------------------------------------------------

    def init(self, key: Array):
        """Initialise (and store) model params."""
        if self.task == "lm":
            from repro.models import transformer as T

            self.params = T.init_params(key, self.cfg)
            return self.params
        init = ST.init_vit if self.task == "vit" else ST.init_gpt
        self.params = init(key, self.cfg)
        return self.params

    def program(self, key: Array, params: Any = None):
        """Program the float weights onto simulated PCM crossbars.

        Replaces every linear leaf by its programmed
        :class:`~repro.aimc_device.AIMCDeviceState` and switches the sim to
        long-term inference mode (wmode="hw").  Programming is a one-shot
        physical act: calling it on an already-programmed tree raises
        (``ValueError``) instead of silently re-wrapping leaves; the same
        ``key`` always programs the same device state.  For ``task="lm"``
        the generic LM stack's spiking-linear weights (attention q/k/v/o,
        MLP in/out) are programmed and everything else stays digital."""
        params = self.params if params is None else params
        assert params is not None, "call init() first or pass params"
        if AD.is_programmed(params):
            raise ValueError(
                "engine.program(): params already hold programmed PCM state; "
                "programming is one-shot — use drift_to()/recalibrate() to "
                "advance the device lifecycle"
            )
        if self.task == "lm":
            self.params = AD.program_lm_tree(key, params, self.sim.cfg)
        else:
            self.params = ST.program_model(key, params, self.sim.cfg)
        self.sim = dataclasses.replace(self.sim, wmode="hw")
        if self.sim.t_seconds > 0:  # engine built with a nonzero device age
            self.params = AD.drift_tree(self.params, self.sim.t_seconds,
                                        self.sim.cfg)
        return self.params

    def drift_to(self, t_seconds: float, params: Any = None):
        """Advance the programmed device clock to ``t_seconds``.

        Pure leaf-value update (shapes/dtypes unchanged), so jitted
        consumers of the params — ``jit_forward`` closures, the serving
        ``decode_step`` — are not recompiled."""
        params = self.params if params is None else params
        self._require_device_state(params)
        self.params = AD.drift_tree_jit(
            params, jnp.float32(t_seconds), self.sim.cfg)
        self.sim = dataclasses.replace(self.sim, t_seconds=float(t_seconds))
        return self.params

    @staticmethod
    def _require_device_state(params) -> None:
        if not AD.has_device_state(params):
            raise ValueError(
                "the drift lifecycle needs AIMCDeviceState leaves — call "
                "engine.program() first (legacy {'hw': dict} trees carry no "
                "device clock and cannot be aged or recalibrated)"
            )

    def recalibrate(self, params: Any = None):
        """Run global drift compensation (GDC, §V-B) at the current device
        time: fold the measured calibration gain into the per-column scales
        of every programmed crossbar."""
        params = self.params if params is None else params
        self._require_device_state(params)
        self.params = AD.recalibrate_tree_jit(params, self.sim.cfg)
        return self.params

    # -- forward -------------------------------------------------------

    def forward(self, x: Array, rng: Array, params: Any = None, *,
                metering: bool = False):
        """Full model forward: images -> class logits (vit), feature
        sequences -> per-token symbol logits (gpt), or token ids [B,S] ->
        next-token logits (lm).

        With ``metering=True`` the spiking primitives run through a
        :class:`MeteringBackend` and the call returns ``(logits, report)``
        where ``report`` is a :class:`repro.energy.model.EnergyReport` —
        measured spike counts x Table-II op energies.  Metering syncs the
        host per primitive call, so it is for eager forwards only."""
        params = self.params if params is None else params
        assert params is not None, "call init() first or pass params"
        backend = MeteringBackend(self.backend) if metering else self.backend
        if self.task == "lm":
            from repro.models import transformer as T

            logits, _ = T.forward(params, {"tokens": x}, self.cfg, rng=rng,
                                  backend=backend, remat="none")
        else:
            fwd = ST.vit_forward if self.task == "vit" else ST.gpt_forward
            logits = fwd(params, x, self.cfg, self.sim, rng, backend=backend)
        if metering:
            return logits, backend.report
        return logits

    def jit_forward(self):
        """A jitted pure function ``(params, x, rng) -> logits`` over the
        engine's (cfg, sim, backend) — for serving / benchmarking loops."""
        cfg, sim, backend = self.cfg, self.sim, self.backend
        if self.task == "lm":
            from repro.models import transformer as T

            return jax.jit(
                lambda params, x, rng: T.forward(
                    params, {"tokens": x}, cfg, rng=rng, backend=backend,
                    remat="none")[0]
            )
        fwd = ST.vit_forward if self.task == "vit" else ST.gpt_forward
        return jax.jit(
            lambda params, x, rng: fwd(params, x, cfg, sim, rng, backend=backend)
        )

    # -- task helpers --------------------------------------------------

    def classify(self, images: Array, rng: Array, params: Any = None) -> Array:
        """[B,H,W,C] images -> [B] predicted class labels."""
        assert self.task == "vit", "classify() is the ViT task"
        return jnp.argmax(self.forward(images, rng, params), axis=-1)

    def detect_symbols(self, feats: Array, rng: Array, params: Any = None) -> Array:
        """[B,L,feat] received-signal features -> [B,L] detected symbols."""
        assert self.task == "gpt", "detect_symbols() is the GPT/ICL task"
        return jnp.argmax(self.forward(feats, rng, params), axis=-1)

    # -- serving (task="lm") -------------------------------------------

    def scheduler(
        self,
        *,
        slots: int = 4,
        cache_len: int = 64,
        params: Any = None,
        pctx: Any = None,
        moe_impl: Optional[str] = None,
        drift: Any = None,
        paged: bool = False,
        page_len: int = 8,
        n_pages: Optional[int] = None,
        decode_kernel: str = "auto",
    ):
        """A :class:`repro.serving.BatchScheduler` over this engine.

        The scheduler's batched ``decode_step`` runs through this engine's
        backend, so reference / integer / pallas serve identically (the
        integer oracle is the bit-exactness contract).  ``paged=True``
        serves spiking SSA configs off the block-paged spike-train KV
        cache (exact prefix sharing + chunked prefill) — bit-identical
        tokens to dense serving.  ``decode_kernel`` picks the kernel
        strategy via :func:`repro.kernels.plan.build_decode_plan`:
        ``"auto"`` runs the fused decode megakernel where (config,
        backend) support it, ``"fused"`` demands it, ``"unfused"`` forces
        the per-primitive path — all bit-identical tokens.  Schedulers are
        cached per (slots, cache_len, moe_impl, paged geometry, kernel)
        and reset on reuse, so repeated :meth:`serve`/:meth:`generate`
        calls keep the compiled decode/prefill functions warm."""
        from repro.serving import BatchScheduler

        assert self.task == "lm", "serving drives the generic LM stack (task='lm')"
        params = self.params if params is None else params
        assert params is not None, "call init() first or pass params"
        key = (slots, cache_len, moe_impl, paged, decode_kernel) + (
            (page_len, n_pages) if paged else ())
        sch = self._schedulers.get(key) if pctx is None else None
        if sch is not None:
            sch.reset()
            sch.set_params(params)
            sch.drift = drift
            return sch
        sch = BatchScheduler(
            params, self.cfg, self.backend, slots=slots, cache_len=cache_len,
            pctx=pctx, moe_impl=moe_impl, drift=drift, paged=paged,
            page_len=page_len, n_pages=n_pages, decode_kernel=decode_kernel,
        )
        if pctx is None:
            self._schedulers[key] = sch
        return sch

    def serve(
        self,
        prompts,
        max_new: int = 16,
        *,
        slots: int = 4,
        cache_len: int = 64,
        seed: int = 0,
        params: Any = None,
        pctx: Any = None,
        moe_impl: Optional[str] = None,
        drift: Any = None,
        paged: bool = False,
        page_len: int = 8,
        n_pages: Optional[int] = None,
        decode_kernel: str = "auto",
    ):
        """Continuous-batching serve: prompts -> (outputs, ServeStats).

        Every request gets the PRN stream ``seed + i`` so results are
        reproducible and independent of batching/admission order.  Pass a
        :class:`repro.aimc_device.DriftPolicy` as ``drift`` (with
        programmed params) to run the PCM drift/recalibration lifecycle;
        per-request energy lands in the scheduler's ``request_energy_j``
        and the returned stats.  ``paged=True`` serves off the block-paged
        spike-train KV cache with exact prefix reuse and chunked prefill."""
        sch = self.scheduler(slots=slots, cache_len=cache_len, params=params,
                             pctx=pctx, moe_impl=moe_impl, drift=drift,
                             paged=paged, page_len=page_len, n_pages=n_pages,
                             decode_kernel=decode_kernel)
        rids = [sch.submit(p, max_new, seed=seed + i) for i, p in enumerate(prompts)]
        outs = sch.run()
        if params is None and sch._programmed:
            # drift is physical: adopt the aged/recalibrated device state so
            # a later serve() (which re-seeds the cached scheduler from
            # self.params) cannot rejuvenate the PCM clock
            self.params = sch.params
        return [outs[r] for r in rids], sch.stats

    def generate(self, prompts, max_new: int = 16, **kwargs):
        """Batch decode: list of token-id prompts -> list of generated
        token-id lists (greedy).  Thin wrapper over :meth:`serve`."""
        outs, _ = self.serve(prompts, max_new, **kwargs)
        return outs

    # -- distributed (mesh) execution ----------------------------------

    def executor(self, mesh, **kwargs):
        """A :class:`repro.distributed.Executor` over this engine's params:
        the whole inference stack placed on a ``(data, model)`` mesh —
        tensor-parallel spiking kernels on ``model``, data-parallel
        continuous batching on ``data``.  Sharded execution on the
        integer/pallas backends is bit-exact vs this engine run on one
        device; see README "Distributed serving"."""
        from repro.distributed import Executor

        assert self.task == "lm", "the distributed executor serves task='lm'"
        assert self.params is not None, "call init() first"
        return Executor(self.params, self.cfg, self.backend, mesh, **kwargs)
