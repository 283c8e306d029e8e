"""Plain reference of the spiking GPT decoder as served (paper Table I,
Xpikeformer column; Eqs. 1-6), written from the model's description in
``jax.numpy`` with nothing taken from the program.

One position of one request, given its token, its position ``p`` and its
comparator stream id ``seed`` (a per-request seed while decoding; while
the prompt is fed, a key of the token prefix up to ``p``, so that shared
prefixes give identical spikes):

1. Keys: ``k = fold_in([0, seed], p)``; the input code draws from
   ``fold_in(k, 0)``, layer ``l``'s attention comparators from
   ``fold_in(k, 1 + l)``, head ``h``'s from ``fold_in(that, h)`` split in two.
2. Input: ``x = E[token] * sqrt(d)``; ``T`` Bernoulli spikes
   ``u_t < sigmoid(x)`` with ``u`` uniform.
3. Each layer: Q, K, V = LIF(crossbar(s)); the new K/V spike trains join
   the request's cache at ``p``; SSA over the whole cache capacity ``L``:
   score spike ``[q.k_j > r_s]`` with ``r_s ~ U{0..hd-1}`` per position,
   attention spike ``[sum_j score_j v_j > r_a]`` with ``r_a ~ U{0..L-1}``;
   ``s += LIF(crossbar_O(a))``; ``s += LIF(crossbar_2(LIF(crossbar_1(s))))``.
4. Crossbars hold each column's weights as levels in ``[-m, m]``,
   ``m = 2**(weight_bits-1) - 1``, scaled by ``max|w| / m``; the LIF
   membrane is ``v = beta v + I_t``, fires at ``v >= threshold`` and resets
   to 0.
5. Output: LayerNorm(mean over T of s) times the unembedding.

``dtype`` is the precision of every real-valued step (coding, crossbar
currents, membranes, norm, head): float32 as the configuration states, or
a lower one for the control.  Products of spikes and levels are exact at
the highest precision; the float head runs at the configuration's
``matmul_precision``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISION = {"default": lax.Precision.DEFAULT, "highest": HIGHEST}


def _splitmix32(x: int) -> int:
    x = (x + 0x9E3779B9) & 0xFFFFFFFF
    x ^= x >> 16
    x = (x * 0x21F0AAAD) & 0xFFFFFFFF
    x ^= x >> 15
    x = (x * 0x735A2D97) & 0xFFFFFFFF
    x ^= x >> 15
    return x


def prefix_keys(tokens: Sequence[int]) -> np.ndarray:
    """Stream id of each prompt position: a splitmix32 hash chain over the
    token prefix, started at ``0x1C0FFEE5``."""
    out = np.empty(len(tokens), np.uint32)
    h = 0x1C0FFEE5
    for i, t in enumerate(tokens):
        h = _splitmix32(h ^ _splitmix32(int(t) & 0xFFFFFFFF))
        out[i] = h
    return out


def quantize(w, weight_bits: int):
    """Per-column levels and scale of a [d_in, d_out] weight matrix."""
    m = 2 ** (weight_bits - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=-2)
    scale = jnp.where(amax > 0, amax / m, 1.0)
    return jnp.clip(jnp.round(w / scale[..., None, :]), -m, m), scale


def lif(cur, beta: float, thr: float):
    """[T, ...] currents -> [T, ...] spikes (float, 0/1)."""
    def step(v, i_t):
        v = beta * v + i_t
        s = (v >= thr).astype(v.dtype)
        return v * (1 - s), s

    _, out = lax.scan(step, jnp.zeros(cur.shape[1:], cur.dtype), cur)
    return out


def spiking_linear(x, lv, scale, c: Dict, dtype):
    """LIF(crossbar(x)) over x [T, B, d_in] (integer-valued)."""
    counts = jnp.einsum("tbi,io->tbo", x.astype(jnp.float32), lv,
                        precision=HIGHEST)
    cur = counts.astype(dtype) * scale.astype(dtype)
    return lif(cur, c["lif_beta"], c["lif_threshold"]).astype(jnp.float32)


def _draws(layer_key, h: int, t: int, hd: int, cap: int):
    """Comparator integers of one slot and layer: rs [T,H,L], ra [T,H,hd]."""
    def head(hi):
        k1, k2 = jax.random.split(jax.random.fold_in(layer_key, hi))
        rs = jax.random.randint(k1, (t, 1, cap), 0, hd, dtype=jnp.int32)
        ra = jax.random.randint(k2, (t, 1, hd), 0, cap, dtype=jnp.int32)
        return rs[:, 0], ra[:, 0]

    rs, ra = jax.vmap(head)(jnp.arange(h))  # [H, T, *]
    return jnp.moveaxis(rs, 0, 1), jnp.moveaxis(ra, 0, 1)


def quantized_layers(w: Dict, c: Dict):
    """Levels and scales of every layer's crossbars, from float weights in
    the served layout (``periods.blk0.{mixer,mlp}``, layer axis first)."""
    d, h, hd, dff = c["d_model"], c["num_heads"], c["head_dim"], c["d_ff"]
    blk = w["periods"]["blk0"]
    mats = {
        "q": blk["mixer"]["wq"].reshape(-1, d, h * hd),
        "k": blk["mixer"]["wk"].reshape(-1, d, c["num_kv_heads"] * hd),
        "v": blk["mixer"]["wv"].reshape(-1, d, c["num_kv_heads"] * hd),
        "o": blk["mixer"]["wo"].reshape(-1, h * hd, d),
        "i": blk["mlp"]["wi"].reshape(-1, d, dff),
        "o2": blk["mlp"]["wo"].reshape(-1, dff, d),
    }
    return {k: quantize(m, c["weight_bits"]) for k, m in mats.items()}


def teacher_forced_logits(w: Dict, c: Dict, tokens, seeds, dtype=jnp.float32):
    """Logits [B, P, V] of B requests fed ``tokens [B, P]`` at positions
    0..P-1 with stream ids ``seeds [B, P]``, from an empty cache of the
    served capacity ``c['cache_len']``."""
    d, h, hd = c["d_model"], c["num_heads"], c["head_dim"]
    kv, t, nl, cap = c["num_kv_heads"], c["spike_T"], c["num_layers"], c["cache_len"]
    assert kv == h, "this reference serves multi-head attention (KV = H)"
    q8 = quantized_layers(w, c)
    table = w["embed"]["table"].astype(jnp.float32)
    unembed = w["unembed"]["w"].astype(jnp.float32)
    ln_scale = w["final_norm"]["scale"].astype(jnp.float32)
    b = tokens.shape[0]
    cache0 = jnp.zeros((nl, 2, b, t, h, cap, hd), jnp.bool_)

    def position(cache, xs):
        p, tok, seed = xs
        base = jnp.stack([jnp.zeros_like(seed), seed], -1).astype(jnp.uint32)
        slot = jax.vmap(lambda k: jax.random.fold_in(k, p))(base)
        enc = jax.vmap(lambda k: jax.random.fold_in(k, 0))(slot)
        x = table[tok] * jnp.sqrt(jnp.float32(d))  # [B, d]
        u = jax.vmap(lambda k: jax.random.uniform(k, (t, 1, d)))(enc)[:, :, 0]
        prob = jnp.clip(jax.nn.sigmoid(x.astype(dtype)), 0, 1)
        s = (u.astype(dtype) < prob[:, None, :]).astype(jnp.float32)
        s = jnp.moveaxis(s, 0, 1)  # [T, B, d]
        new = []
        for li in range(nl):
            lk = jax.vmap(lambda k: jax.random.fold_in(k, 1 + li))(slot)
            rs, ra = jax.vmap(lambda k: _draws(k, h, t, hd, cap))(lk)
            lin = {n: (lv[li], sc[li]) for n, (lv, sc) in q8.items()}
            q = spiking_linear(s, *lin["q"], c, dtype).reshape(t, b, h, hd)
            k_new = spiking_linear(s, *lin["k"], c, dtype).reshape(t, b, h, hd)
            v_new = spiking_linear(s, *lin["v"], c, dtype).reshape(t, b, h, hd)
            kc = cache[li, 0].at[:, :, :, p].set(
                jnp.moveaxis(k_new, 0, 1).astype(jnp.bool_))
            vc = cache[li, 1].at[:, :, :, p].set(
                jnp.moveaxis(v_new, 0, 1).astype(jnp.bool_))
            new.append(jnp.stack([kc, vc]))
            qb = jnp.moveaxis(q, 0, 1)  # [B, T, H, hd]
            score = jnp.einsum("bthd,bthld->bthl", qb, kc.astype(jnp.float32),
                               precision=HIGHEST)
            sp = (score > rs).astype(jnp.float32)
            acc = jnp.einsum("bthl,bthld->bthd", sp, vc.astype(jnp.float32),
                             precision=HIGHEST)
            a = jnp.moveaxis((acc > ra).astype(jnp.float32), 0, 1)
            s = s + spiking_linear(a.reshape(t, b, h * hd), *lin["o"], c, dtype)
            h1 = spiking_linear(s, *lin["i"], c, dtype)
            s = s + spiking_linear(h1, *lin["o2"], c, dtype)
        xr = jnp.mean(s, axis=0).astype(dtype)
        mu = jnp.mean(xr, -1, keepdims=True)
        var = jnp.mean(jnp.square(xr - mu), -1, keepdims=True)
        y = (xr - mu) * lax.rsqrt(var + jnp.asarray(c["layernorm_eps"], dtype))
        y = y * ln_scale.astype(dtype)
        logits = jnp.dot(y, unembed.astype(dtype),
                         precision=PRECISION[c["matmul_precision"]])
        return jnp.stack(new), logits.astype(jnp.float32)

    ps = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    _, logits = lax.scan(position, cache0,
                         (ps, jnp.moveaxis(tokens, 0, 1), jnp.moveaxis(seeds, 0, 1)))
    return jnp.moveaxis(logits, 0, 1)


def teacher_inputs(samples: List[Dict]):
    """Token and stream-id arrays of served requests (``prompt``,
    ``tokens`` served, ``seed``), padded to one length."""
    lens = [len(r["prompt"]) + len(r["tokens"]) - 1 for r in samples]
    p = max(lens)
    toks = np.zeros((len(samples), p), np.int32)
    seeds = np.zeros((len(samples), p), np.uint32)
    for i, r in enumerate(samples):
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        n_ctx = len(r["prompt"]) - 1
        toks[i, :len(seq)] = seq
        seeds[i, :n_ctx] = prefix_keys(r["prompt"][:-1])
        seeds[i, n_ctx:len(seq)] = np.uint32(r["seed"])
    return toks, seeds


def served_gaps(logits, samples: List[Dict]) -> np.ndarray:
    """For every served token, how far its logit lies below the best one."""
    lg = np.asarray(logits)
    out = []
    for i, r in enumerate(samples):
        n_ctx = len(r["prompt"]) - 1
        for j, tok in enumerate(r["tokens"]):
            row = lg[i, n_ctx + j]
            out.append(float(row.max() - row[tok]))
    return np.asarray(out)


def first_choices(logits, samples: List[Dict]) -> List[List[int]]:
    """The token each position's logits put first, at the served positions."""
    lg = np.asarray(logits)
    return [[int(lg[i, len(r["prompt"]) - 1 + j].argmax())
             for j in range(len(r["tokens"]))] for i, r in enumerate(samples)]


def compare(w: Dict, c: Dict, samples: List[Dict], block: int = 8) -> Dict:
    """Widest gap of a served token below the reference's best logit."""
    gaps = []
    fn = jax.jit(lambda w_, t_, s_: teacher_forced_logits(w_, c, t_, s_))
    for i in range(0, len(samples), block):
        part = samples[i:i + block]
        toks, seeds = teacher_inputs(part)
        gaps.append(served_gaps(fn(w, jnp.asarray(toks), jnp.asarray(seeds)), part))
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()), "tokens_compared": int(g.size)}


def control(w: Dict, c: Dict, samples: List[Dict], dtype=jnp.bfloat16,
            block: int = 8) -> Dict:
    """The reference in a lower precision in the program's place: at every
    served position, the gap (under the float32 reference) of the token the
    lower precision puts first."""
    fn32 = jax.jit(lambda w_, t_, s_: teacher_forced_logits(w_, c, t_, s_))
    fnlo = jax.jit(lambda w_, t_, s_: teacher_forced_logits(w_, c, t_, s_, dtype))
    gaps = []
    for i in range(0, len(samples), block):
        part = samples[i:i + block]
        toks, seeds = map(jnp.asarray, teacher_inputs(part))
        picks = first_choices(fnlo(w, toks, seeds), part)
        as_served = [dict(r, tokens=pk) for r, pk in zip(part, picks)]
        gaps.append(served_gaps(fn32(w, toks, seeds), as_served))
    g = np.concatenate(gaps)
    return {"max_logit_gap": float(g.max()), "tokens_compared": int(g.size)}
