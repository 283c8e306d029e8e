"""Mixture-of-Experts with 2-D expert parallelism.

Two interchangeable implementations:

* ``moe_dense``  — every expert computed on every token, combined with the
  router's top-k weights.  O(E) FLOPs: only for reduced smoke-test configs.

* ``moe_ep``     — production path under ``jax.shard_map``.  Experts are
  sharded over the ``model`` mesh axis (expert parallelism, EP) and each
  expert's d_ff dimension is sharded over the ``data`` axis (expert tensor
  parallelism) so that a 480B-expert bank (arctic) fits 256 chips.  Token
  routing is sort-based (MegaBlocks-style, no O(T*E*C) one-hot dispatch
  tensors) with fixed per-destination capacity and drop-on-overflow:

      sender (i,j):   sort (token,choice) pairs by destination column,
                      pack send buffer [M, C, d]
      all_to_all over "model":    route tokens to their expert column
      all_gather over "data":     un-shard the d_ff dimension of the local
                                  experts' weights (ZeRO-3: weights live
                                  sharded, are gathered just-in-time per
                                  layer, and gradients reduce-scatter back
                                  via the shard_map transpose)
      expert compute:  [E_l, C2, d] x [E_l, d, f] -> act -> [E_l, C2, d]
      all_to_all back over "model", weighted combine at the sender.

  Gathering *weights* (O(E_l * d * f) once per layer) instead of *tokens*
  (O(16x tokens * d) per layer) keeps both the transient memory and the
  ICI bytes bounded at arctic-480b scale — see EXPERIMENTS.md §Perf.

  The ``pod`` axis is untouched: expert weights are replicated across pods
  and each pod routes its own tokens (hierarchical EP — no inter-pod
  all-to-all, which would cross the slow DCN links).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.layers import ParamDef, _act

Array = jax.Array

_shard_map = partial(jax.shard_map, check_vma=False)


# ---------------------------------------------------------------------------
# Parallel context — how the surrounding program is laid out on the mesh
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Mesh axis bookkeeping threaded through the model."""

    mesh: Any = None  # jax.sharding.Mesh | None (None => single-device)
    dp_axes: Tuple[str, ...] = ()  # batch axes, e.g. ("pod", "data")
    fsdp_axis: Optional[str] = None  # "data" (d_ff shard of experts, ZeRO)
    tp_axis: Optional[str] = None  # "model"
    seq_shard: bool = True  # activations [B,S,d]: S over tp_axis

    @property
    def tp_size(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def fsdp_size(self) -> int:
        if self.mesh is None or self.fsdp_axis is None:
            return 1
        return self.mesh.shape[self.fsdp_axis]

    def x_spec(self, seq_sharded: bool) -> P:
        b = self.dp_axes if self.dp_axes else None
        s = self.tp_axis if (seq_sharded and self.tp_axis) else None
        return P(b, s, None)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def moe_schema(cfg) -> Dict[str, ParamDef]:
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    s = {
        "router": ParamDef((d, e), ("embed", "experts_r")),
        "wi": ParamDef((e, d, f), ("experts", "expert_embed", "expert_ffn")),
        "wo": ParamDef((e, f, d), ("experts", "expert_ffn", "expert_embed")),
    }
    if cfg.gated_mlp:
        s["wg"] = ParamDef((e, d, f), ("experts", "expert_embed", "expert_ffn"))
    return s


# ---------------------------------------------------------------------------
# Router
# ---------------------------------------------------------------------------


def router(params, x: Array, cfg) -> Tuple[Array, Array, Array]:
    """Top-k routing. Returns (weights [.. ,k], idx [.., k] int32, aux_loss)."""
    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = lax.top_k(probs, cfg.moe_top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    # Switch-style load-balance auxiliary loss.
    e = cfg.num_experts
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    one_hot = jax.nn.one_hot(idx.reshape(-1, cfg.moe_top_k), e, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(one_hot, axis=1), axis=0)
    aux = e * jnp.sum(me * ce)
    return weights.astype(x.dtype), idx.astype(jnp.int32), aux


# ---------------------------------------------------------------------------
# Dense (reference / smoke-test) implementation
# ---------------------------------------------------------------------------


def moe_dense(params, x: Array, cfg) -> Tuple[Array, Array]:
    weights, idx, aux = router(params, x, cfg)
    h = jnp.einsum("bsd,edf->bsef", x, params["wi"].astype(x.dtype))
    if "wg" in params:
        g = jnp.einsum("bsd,edf->bsef", x, params["wg"].astype(x.dtype))
        h = _act(cfg.act, g) * h
    else:
        h = _act(cfg.act, h)
    y_all = jnp.einsum("bsef,efd->bsed", h, params["wo"].astype(x.dtype))
    sel = jax.nn.one_hot(idx, cfg.num_experts, dtype=x.dtype)  # [B,S,k,E]
    comb = jnp.einsum("bske,bsk->bse", sel, weights)
    return jnp.einsum("bsed,bse->bsd", y_all, comb), aux


# ---------------------------------------------------------------------------
# Sort-based EP implementation (shard_map)
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _ep_local(
    x: Array,  # [T_l, d]   local tokens
    weights: Array,  # [T_l, k]
    idx: Array,  # [T_l, k]   global expert ids
    wi: Array,  # [E_l, d, f_l]
    wg: Optional[Array],
    wo: Array,  # [E_l, f_l, d]
    *,
    cfg,
    tp_axis: Optional[str],
    fsdp_axis: Optional[str],
    tp_size: int,
    fsdp_size: int,
    capacity: int,
    token_gather: bool = False,
) -> Array:
    """Per-device body of the EP MoE (runs inside shard_map)."""
    T_l, d = x.shape
    k = cfg.moe_top_k
    E = cfg.num_experts
    E_l = E // tp_size  # local experts per model column
    M, C = tp_size, capacity
    Pn = T_l * k

    # ---- 1. flatten (token, choice) pairs, sort by destination column ----
    flat_idx = idx.reshape(Pn)
    flat_w = weights.reshape(Pn)
    tok_of = jnp.arange(Pn, dtype=jnp.int32) // k
    dest = flat_idx // E_l  # destination model column
    local_e = flat_idx % E_l  # expert within the column
    order = jnp.argsort(dest, stable=True)
    dest_s, tok_s, le_s = dest[order], tok_of[order], local_e[order]
    # rank within destination group
    starts = jnp.cumsum(jnp.bincount(dest_s, length=M)) - jnp.bincount(dest_s, length=M)
    rank = jnp.arange(Pn, dtype=jnp.int32) - starts[dest_s].astype(jnp.int32)
    keep = rank < C
    slot = jnp.where(keep, rank, C - 1)

    send_x = jnp.zeros((M, C, d), x.dtype)
    send_x = send_x.at[dest_s, slot].add(jnp.where(keep[:, None], x[tok_s], 0))
    send_e = jnp.full((M, C), E_l, jnp.int32)  # E_l == "empty" sentinel
    send_e = send_e.at[dest_s, slot].min(jnp.where(keep, le_s, E_l))

    # ---- 2. route to the expert column ----
    if tp_axis is not None:
        recv_x = lax.all_to_all(send_x, tp_axis, 0, 0, tiled=True)
        recv_e = lax.all_to_all(send_e, tp_axis, 0, 0, tiled=True)
    else:
        recv_x, recv_e = send_x, send_e
    rx = recv_x.reshape(M * C, d)
    re = recv_e.reshape(M * C)
    Tg = rx.shape[0]

    # ---- 3. un-shard the d_ff dimension: gather WEIGHTS or TOKENS ----
    # Training (many tokens): gather the d_ff-sharded expert weights
    # (ZeRO-3, O(E_l*d*f) per layer).  Decode (few tokens): gather the
    # tokens over the data axis instead (O(R*M*C*d), kilobytes at decode)
    # and psum_scatter the f_l-partial outputs back — §Perf HC2.
    if (not token_gather) and fsdp_axis is not None and fsdp_size > 1:
        wi = lax.all_gather(wi, fsdp_axis, axis=2, tiled=True)  # [E_l, d, f]
        wo = lax.all_gather(wo, fsdp_axis, axis=1, tiled=True)  # [E_l, f, d]
        if wg is not None:
            wg = lax.all_gather(wg, fsdp_axis, axis=2, tiled=True)
    if token_gather and fsdp_axis is not None and fsdp_size > 1:
        rx = lax.all_gather(rx, fsdp_axis, axis=0, tiled=True)  # [R*M*C, d]
        re = lax.all_gather(re, fsdp_axis, axis=0, tiled=True)
        Tg = rx.shape[0]

    # ---- 4. group by local expert (sort + fixed capacity), compute ----
    C2 = _round_up(min(Tg, max(int(Tg // max(E_l, 1) * 1.25), 8)), 8)
    order2 = jnp.argsort(re, stable=True)
    re_s = re[order2]
    cnt = jnp.bincount(re_s, length=E_l + 1)
    st = jnp.cumsum(cnt) - cnt
    rank2 = jnp.arange(Tg, dtype=jnp.int32) - st[re_s].astype(jnp.int32)
    keep2 = (rank2 < C2) & (re_s < E_l)
    slot2 = jnp.where(keep2, rank2, C2 - 1)
    eid2 = jnp.where(keep2, re_s, 0)

    xg = jnp.zeros((E_l, C2, d), x.dtype)
    xg = xg.at[eid2, slot2].add(jnp.where(keep2[:, None], rx[order2], 0))

    h = jnp.einsum("ecd,edf->ecf", xg, wi.astype(x.dtype))
    if wg is not None:
        h = _act(cfg.act, jnp.einsum("ecd,edf->ecf", xg, wg.astype(x.dtype))) * h
    else:
        h = _act(cfg.act, h)
    yg = jnp.einsum("ecf,efd->ecd", h, wo.astype(x.dtype))

    # ---- 5. un-group, return to origin row ----
    ry = jnp.zeros((Tg, d), x.dtype)
    ry = ry.at[order2].add(jnp.where(keep2[:, None], yg[eid2, slot2], 0))
    if token_gather and fsdp_axis is not None and fsdp_size > 1:
        # sum the f_l partial outputs AND return each token to its row
        ry = lax.psum_scatter(ry, fsdp_axis, scatter_dimension=0, tiled=True)
    ry = ry.reshape(M, C, d)

    # ---- 6. route back and combine at the sender ----
    if tp_axis is not None:
        back = lax.all_to_all(ry, tp_axis, 0, 0, tiled=True)
    else:
        back = ry
    y = jnp.zeros((T_l, d), x.dtype)
    contrib = jnp.where(keep[:, None], back[dest_s, slot] * flat_w[order][:, None], 0)
    y = y.at[tok_s].add(contrib)
    return y


def moe_ep(params, x: Array, cfg, pctx: ParallelCtx, *, seq_sharded: bool) -> Tuple[Array, Array]:
    """EP MoE: router outside (GSPMD), dispatch/compute inside shard_map."""
    b, s, d = x.shape
    weights, idx, aux = router(params, x, cfg)

    tp = pctx.tp_size
    fs = pctx.fsdp_size
    # per-device local token count
    denom = tp if (seq_sharded and pctx.tp_axis) else 1
    for ax in pctx.dp_axes:
        denom *= pctx.mesh.shape[ax] if pctx.mesh is not None else 1
    T_l = max((b * s) // max(denom, 1), 1)
    cap = _round_up(int(T_l * cfg.moe_top_k * cfg.capacity_factor / tp) + 1, 8)

    # strategy: gather whichever is smaller — expert weights (training) or
    # the routed tokens (decode); see _ep_local step 3.
    n_mats = 3 if "wg" in params else 2
    weight_bytes = (cfg.num_experts // max(tp, 1)) * cfg.d_model * cfg.d_ff * n_mats
    token_bytes = 2 * fs * tp * cap * cfg.d_model  # gather + psum_scatter
    token_gather = token_bytes < weight_bytes

    body = partial(
        _ep_local,
        cfg=cfg,
        tp_axis=pctx.tp_axis,
        fsdp_axis=pctx.fsdp_axis,
        tp_size=tp,
        fsdp_size=fs,
        capacity=cap,
        token_gather=token_gather,
    )

    gated = "wg" in params

    def mapped(xl, wl, il, wi, wo, *maybe_wg):
        bl, sl, _ = xl.shape
        y = body(
            xl.reshape(bl * sl, d),
            wl.reshape(bl * sl, -1),
            il.reshape(bl * sl, -1),
            wi,
            maybe_wg[0] if maybe_wg else None,
            wo,
        )
        return y.reshape(bl, sl, d)

    extra = (params["wg"],) if gated else ()
    if pctx.mesh is None:
        y = mapped(x, weights, idx, params["wi"], params["wo"], *extra)
        return y, aux

    xs = pctx.x_spec(seq_sharded)
    wspec_in = P(pctx.tp_axis, None, pctx.fsdp_axis)  # wi/wg [E, d, f_l]
    wspec_out = P(pctx.tp_axis, pctx.fsdp_axis, None)  # wo [E, f_l, d]
    in_specs = (xs, xs, xs, wspec_in, wspec_out) + ((wspec_in,) if gated else ())
    y = _shard_map(
        mapped,
        mesh=pctx.mesh,
        in_specs=in_specs,
        out_specs=xs,
    )(x, weights, idx, params["wi"], params["wo"], *extra)
    return y, aux


def moe_apply(
    params, x: Array, cfg, pctx: ParallelCtx, *, impl: str = "ep_a2a", seq_sharded: bool = True
) -> Tuple[Array, Array]:
    if impl == "dense":
        return moe_dense(params, x, cfg)
    return moe_ep(params, x, cfg, pctx, seq_sharded=seq_sharded)
