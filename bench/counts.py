"""Operations and bytes the algorithm needs, computed from shapes.

Every count is the algorithm's *least* representation, so that no later
implementation can read above 100 % of a roofline or of a peak:

* a crossbar MAC is one spike-step x one stored weight level, counted as
  two operations (multiply and add), as the chip's int8 peak counts them;
* stochastic spiking attention (SSA) counts its AND-and-add work over the
  *live* cached positions only, never over the cache capacity;
* spikes are bits: a cached K/V spike costs 1/8 byte;
* weights are their programmed levels, ``weight_bits`` bits each (5 for
  the paper's differential PCM pair);
* comparator random numbers cost 0 bytes: they can be made on the chip;
* LIF updates, residual adds and rate coding are left out (they only
  lower a bound).

Configuration dictionaries are the files under ``bench/configs``.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


def _lm_dims(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    hd = c["head_dim"]
    return (c["d_model"], c["num_heads"], c["num_kv_heads"], hd, c["d_ff"],
            c["spike_T"], c["num_layers"])


def lm_layer_weights(c: Dict) -> int:
    """Stored crossbar weights of one spiking decoder layer (Q, K, V, O,
    FFN in and out)."""
    d, h, kv, hd, dff, _, _ = _lm_dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 2 * d * dff


def lm_layer_ops(c: Dict, live: int) -> int:
    """Operations of one decoder layer for one position whose query sees
    ``live`` cached positions (its own included)."""
    d, h, kv, hd, dff, t, _ = _lm_dims(c)
    crossbar = 2 * t * lm_layer_weights(c)
    ssa = 2 * t * h * live * hd * 2  # scores q.k, then s.v
    return crossbar + ssa


def lm_position_ops(c: Dict, live: int) -> int:
    """Operations of the whole model for one position (all layers and the
    LM head)."""
    return (c["num_layers"] * lm_layer_ops(c, live)
            + 2 * c["d_model"] * c["vocab_size"])


def lm_step_layer_bytes(c: Dict, lives: Iterable[int]) -> float:
    """Least HBM bytes one layer's decode kernel moves in one batched step:
    its weights once, the live K/V spikes of every occupied slot, and the
    new K/V spikes it writes.  ``lives`` holds each occupied slot's live
    positions (the new one included)."""
    d, h, kv, hd, dff, t, _ = _lm_dims(c)
    lives = list(lives)
    kv_bits_per_pos = 2 * t * kv * hd
    w_bits = lm_layer_weights(c) * c["weight_bits"]
    read_bits = sum(max(live - 1, 0) for live in lives) * kv_bits_per_pos
    write_bits = len(lives) * kv_bits_per_pos
    return (w_bits + read_bits + write_bits) / 8.0


def lm_step_layer_ops(c: Dict, lives: Iterable[int]) -> int:
    """Operations of one layer in one batched step over occupied slots."""
    return sum(lm_layer_ops(c, live) for live in lives)


def least_time_s(ops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peaks["int8_ops"]
    t_mem = nbytes / peaks["hbm_bytes_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def vit_image_ops(c: Dict) -> int:
    """Operations of one image through the spiking ViT: patch embedding,
    every encoder layer's crossbars and full-sequence SSA, and the head."""
    d, h, t, depth = c["dim"], c["num_heads"], c["T"], c["depth"]
    hd = d // h
    n = (c["image_size"] // c["patch_size"]) ** 2
    dff = c["mlp_ratio"] * d
    pdim = c["patch_size"] ** 2 * c["in_channels"]
    crossbar = 2 * t * n * (4 * d * d + 2 * d * dff)
    ssa = 2 * t * h * n * n * hd * 2
    return (depth * (crossbar + ssa) + 2 * n * pdim * d
            + 2 * d * c["num_classes"])
