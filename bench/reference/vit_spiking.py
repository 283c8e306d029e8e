"""Plain reference of the spiking ViT classifier (paper Table III; Table I
Xpikeformer column; Eqs. 1-6), written from the model's description in
``jax.numpy`` with nothing taken from the program.

For a batch of images and its key ``rng``:

1. ``k_embed, k_code, k_blocks, k_head = split(rng, 4)``.
2. Patches of ``p x p`` pixels (row-major patch grid, pixels in row, column,
   channel order) go through the float patch embedding plus the position
   table; ``T`` Bernoulli spikes ``u_t < sigmoid(x)``, ``u`` uniform from
   ``k_code`` over ``[T, B, N, d]``.
3. Layer ``i`` draws its attention comparators from the last of
   ``split(fold_in(k_blocks, i), 7)``, split in two: ``r_s ~ U{0..hd-1}``
   over ``[T*B*H, N, N]`` and ``r_a ~ U{0..N-1}`` over ``[T*B*H, N, hd]``.
   Q, K, V = LIF(crossbar(s)); score spikes ``[q.k > r_s]``, attention
   spikes ``[score.v > r_a]`` (no mask); ``s += LIF(crossbar_O(a))``;
   ``s += LIF(crossbar_2(LIF(crossbar_1(s))))``.  Crossbars hold levels in
   ``[-m, m]`` per column scaled by ``max|w| / m`` plus the float bias.
4. Logits: the mean over T and over patches of ``s``, through the float
   head.

``dtype`` is the precision of every real-valued step; products of spikes
and levels are exact at the highest precision, the float patch embedding
and head run at the configuration's ``matmul_precision``.  The comparator draws depend on the whole
batch's shape, so they are drawn for the batch and the layers then run in
blocks of images.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISION = {"default": lax.Precision.DEFAULT, "highest": HIGHEST}


def quantize(w, weight_bits: int):
    m = 2 ** (weight_bits - 1) - 1
    amax = jnp.max(jnp.abs(w), axis=-2)
    scale = jnp.where(amax > 0, amax / m, 1.0)
    return jnp.clip(jnp.round(w / scale[..., None, :]), -m, m), scale


def lif(cur, beta: float, thr: float):
    def step(v, i_t):
        v = beta * v + i_t
        s = (v >= thr).astype(v.dtype)
        return v * (1 - s), s

    _, out = lax.scan(step, jnp.zeros(cur.shape[1:], cur.dtype), cur)
    return out


def spiking_linear(x, p, c: Dict, dtype):
    lv, scale = quantize(p["w"], c["weight_bits"])
    counts = jnp.einsum("t...i,io->t...o", x, lv, precision=HIGHEST)
    cur = counts.astype(dtype) * scale.astype(dtype) + p["b"].astype(dtype)
    return lif(cur, c["lif_beta"], c["lif_threshold"]).astype(jnp.float32)


def _heads(x, h):  # [T,B,N,d] -> [T,B,H,N,hd]
    t, b, n, d = x.shape
    return jnp.moveaxis(x.reshape(t, b, n, h, d // h), 3, 2)


def _unheads(x):  # [T,B,H,N,hd] -> [T,B,N,d]
    t, b, h, n, hd = x.shape
    return jnp.moveaxis(x, 2, 3).reshape(t, b, n, h * hd)


def logits(w: Dict, c: Dict, images, rng, dtype=jnp.float32, block: int = 64):
    d, h, t = c["dim"], c["num_heads"], c["T"]
    ps = c["patch_size"]
    bsz, size = images.shape[0], images.shape[1]
    g = size // ps
    n = g * g
    hd = d // h
    k_embed, k_code, k_blocks, k_head = jax.random.split(rng, 4)
    x = images.reshape(bsz, g, ps, g, ps, images.shape[-1])
    x = jnp.moveaxis(x, 3, 2).reshape(bsz, n, -1)
    prec = PRECISION[c["matmul_precision"]]
    x = (jnp.einsum("bnp,pd->bnd", x.astype(dtype), w["patch"]["w"].astype(dtype),
                    precision=prec)
         + w["patch"]["b"].astype(dtype) + w["pos"].astype(dtype))
    u = jax.random.uniform(k_code, (t, bsz, n, d))
    s = (u.astype(dtype) < jnp.clip(jax.nn.sigmoid(x), 0, 1)[None]).astype(jnp.float32)
    for i, bp in enumerate(w["blocks"]):
        kr = jax.random.split(jax.random.fold_in(k_blocks, i), 7)[6]
        k1, k2 = jax.random.split(kr)
        rs = jax.random.randint(k1, (t * bsz * h, n, n), 0, hd, dtype=jnp.int32)
        ra = jax.random.randint(k2, (t * bsz * h, n, hd), 0, n, dtype=jnp.int32)
        rs = rs.reshape(t, bsz, h, n, n)
        ra = ra.reshape(t, bsz, h, n, hd)
        outs = []
        for b0 in range(0, bsz, block):
            sb = s[:, b0:b0 + block]
            q = _heads(spiking_linear(sb, bp["wq"], c, dtype), h)
            k = _heads(spiking_linear(sb, bp["wk"], c, dtype), h)
            v = _heads(spiking_linear(sb, bp["wv"], c, dtype), h)
            sc = jnp.einsum("tbhnd,tbhmd->tbhnm", q, k, precision=HIGHEST)
            sp = (sc > rs[:, b0:b0 + block]).astype(jnp.float32)
            ac = jnp.einsum("tbhnm,tbhmd->tbhnd", sp, v, precision=HIGHEST)
            a = _unheads((ac > ra[:, b0:b0 + block]).astype(jnp.float32))
            sb = sb + spiking_linear(a, bp["wo"], c, dtype)
            hh = spiking_linear(sb, bp["w1"], c, dtype)
            outs.append(sb + spiking_linear(hh, bp["w2"], c, dtype))
        s = jnp.concatenate(outs, axis=1)
    pooled = jnp.mean(jnp.mean(s, axis=0), axis=1).astype(dtype)
    out = (jnp.dot(pooled, w["head"]["w"].astype(dtype), precision=prec)
           + w["head"]["b"].astype(dtype))
    return out.astype(jnp.float32)


def compare(w: Dict, c: Dict, batches) -> Dict:
    """The largest difference of a served logit from the reference's, over
    ``batches`` of (images, rng, served logits)."""
    fn = jax.jit(lambda w_, x_, r_: logits(w_, c, x_, r_))
    errs = [np.abs(np.asarray(lg) - np.asarray(fn(w, x, r))).max(-1)
            for x, r, lg in batches]
    e = np.concatenate(errs)
    return {"max_logit_err": float(e.max()), "images_compared": int(e.size)}


def control(w: Dict, c: Dict, batches, dtype=jnp.bfloat16) -> Dict:
    """The reference in a lower precision in the program's place, read as
    :func:`compare` reads the program."""
    fnlo = jax.jit(lambda w_, x_, r_: logits(w_, c, x_, r_, dtype))
    return compare(w, c, [(x, r, fnlo(w, x, r)) for x, r, _ in batches])
