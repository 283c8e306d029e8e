"""The unified decode-kernel surface: KVView / AttnSpec / DecodePlan.

One small module, three dataclasses, one factory — so every consumer of
the spiking decode path (``models/transformer.py``, ``serving/scheduler``,
``distributed/backend.py``, ``launch/serve.py``) selects kernels from the
same place instead of branching on ``paged=`` flags and positional
``i_max``/``h0`` soup at each call site:

* :class:`KVView` — the K/V storage union a decode step attends over:
  a slot-dense spike-train cache (``page_table is None``) or a block-paged
  pool addressed through a per-slot page table.  Backends take a view and
  dispatch internally; callers stop caring which layout they hold.
* :class:`AttnSpec` — the static attention geometry: logical cache
  capacity ``i_max`` (the comparator PRN range), the tensor-parallel
  global-head offset ``h0``, and the GQA group factor.
* :class:`DecodePlan` — which kernel strategy a serving stack runs:
  ``kernel="fused"`` routes every decoder layer through the single
  megakernel launch (:mod:`repro.kernels.decode_fused`), ``"unfused"``
  keeps the per-primitive path.  Built once per scheduler by
  :func:`build_decode_plan` and closed over by the jitted decode step, so
  kernel selection can never cause a recompile mid-serve.

``build_decode_plan(cfg, backend, kernel="auto")`` resolves ``auto`` to
the fused megakernel exactly where it is supported (spiking SSA stacks of
pure attention blocks on a backend that implements
``decode_layer_fused``) and falls back to the unfused path elsewhere;
``kernel="fused"`` raises instead of silently degrading.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax

Array = jax.Array


@dataclasses.dataclass
class KVView:
    """What a decode step attends over — dense cache or paged pool.

    Dense: ``k``/``v`` are per-slot spike caches ``[B, T, L, KV, hd]``
    (uint8) and ``page_table`` is ``None``.  Paged: ``k``/``v`` are global
    page pools ``[n_pages, T, KV, page_len, hd]`` and ``page_table``
    ``[B, max_pages]`` (int32) maps each slot's logical blocks to physical
    pages (page 0 = the permanently-zero null page)."""

    k: Array
    v: Array
    page_table: Optional[Array] = None

    @property
    def paged(self) -> bool:
        return self.page_table is not None

    @classmethod
    def dense(cls, k: Array, v: Array) -> "KVView":
        return cls(k=k, v=v)

    @classmethod
    def from_pool(cls, kpool: Array, vpool: Array, page_table: Array) -> "KVView":
        return cls(k=kpool, v=vpool, page_table=page_table)


jax.tree_util.register_pytree_node(
    KVView,
    lambda view: ((view.k, view.v, view.page_table), None),
    lambda _, leaves: KVView(*leaves),
)


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static decode-attention geometry.

    ``i_max`` — logical cache capacity: the exclusive upper bound of the
    per-position comparator PRN draws (``r_a ~ U{0..i_max-1}``), which must
    equal the *logical* cache length regardless of physical layout so dense
    and paged serving draw identical streams.  ``h0`` — global index of
    this caller's first head (tensor-parallel shards pass their offset so
    each shard draws exactly the single-device oracle's per-head streams).
    ``groups`` — GQA repeat factor (query heads per KV head), informational
    for dense views (callers pre-repeat) and shape-checked for paged."""

    i_max: int
    h0: Any = 0  # int, or a traced scalar inside shard_map bodies
    groups: int = 1


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """A resolved kernel strategy for one serving stack.

    Hashable and closure-static: the scheduler builds it once and the
    jitted decode step closes over it, preserving the one-compile-per-
    scheduler-lifetime invariant."""

    layout: str = "dense"  # "dense" | "paged"
    kernel: str = "unfused"  # "fused" | "unfused"
    page_len: int = 0  # tokens per KV page (paged layouts)
    reasons: Tuple[str, ...] = ()  # why auto resolved the way it did

    @property
    def fused(self) -> bool:
        return self.kernel == "fused"

    def page_columns(self, slots: int, max_pages: int) -> int:
        """(slot, page-table column) pairs one layer's paged attention
        kernel visits per decode step, live or not.  The fused
        megakernel's grid is ``(slots, max_pages)``
        (:func:`repro.kernels.decode_fused.fused_decode_layer_paged`); the
        unfused kernel's is ``(slots, T, heads, max_pages)``, the same
        columns once per timestep and head.  A grid that skips dead
        columns changes this count with it."""
        if self.layout != "paged":
            raise ValueError(f"page columns of a {self.layout} plan")
        return slots * max_pages

    def describe(self) -> str:
        geo = f", page_len={self.page_len}" if self.layout == "paged" else ""
        why = f" ({'; '.join(self.reasons)})" if self.reasons else ""
        return f"DecodePlan({self.layout}, {self.kernel}{geo}){why}"


def _fused_supported(cfg, backend) -> Tuple[bool, str]:
    """Can this (config, backend) pair run the fused decode megakernel?"""
    if not (getattr(cfg, "spiking", False)
            and getattr(cfg, "attention_kind", "") == "ssa"):
        return False, "fused decode needs a spiking SSA config"
    if not all(m in ("attn", "local") for m in cfg.block_pattern):
        return False, f"non-attention mixers in pattern {cfg.block_pattern}"
    if getattr(cfg, "is_moe", False):
        return False, "MoE FFN tails decode on the rate interface"
    if backend is None or not callable(
            getattr(backend, "decode_layer_fused", None)):
        name = getattr(backend, "name", backend)
        return False, f"backend {name!r} has no decode_layer_fused"
    return True, "fused megakernel supported"


def build_decode_plan(cfg, backend=None, *, layout: str = "dense",
                      kernel: str = "auto", page_len: int = 8) -> DecodePlan:
    """Resolve one :class:`DecodePlan` for a serving stack.

    ``kernel``: ``"auto"`` picks the fused megakernel where supported and
    the unfused per-primitive path elsewhere; ``"fused"`` demands it (and
    raises ``ValueError`` when the config/backend cannot run it);
    ``"unfused"`` forces the per-primitive path.  ``layout`` mirrors the
    scheduler's ``paged=`` choice; ``page_len`` only matters for paged."""
    if layout not in ("dense", "paged"):
        raise ValueError(f"layout must be dense|paged, got {layout!r}")
    if kernel not in ("auto", "fused", "unfused"):
        raise ValueError(f"kernel must be auto|fused|unfused, got {kernel!r}")
    ok, why = _fused_supported(cfg, backend)
    if kernel == "fused" and not ok:
        raise ValueError(f"decode kernel 'fused' unsupported: {why}")
    resolved = "fused" if (kernel == "fused" or (kernel == "auto" and ok)) \
        else "unfused"
    return DecodePlan(layout=layout, kernel=resolved,
                      page_len=page_len if layout == "paged" else 0,
                      reasons=(why,))
