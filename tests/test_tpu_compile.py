"""Chip-compiler rehearsal: the main path's Pallas kernels compile for a TPU v5e.

Interpret mode (the CPU path every other test takes) cannot see what the
TPU's Mosaic compiler refuses: unaligned slices, unsupported casts and
reductions, layouts, fast-memory overruns.  These tests lower and compile
each kernel of the serving and classification paths with ``interpret=False``
for one chip of a *described* ``v5e:2x2`` topology — the compiler is
installed, no chip is attached, nothing runs — at the paper models' full
widths:

* ``xpikeformer-gpt-8-512`` decode: T=4, B=4 slots (and the B=1 prefill
  scan), d=512, 8 heads x 64, d_ff=2048, a 64-position cache, 8-token pages;
* ``xpikeformer-vit-8-768`` classification: T=4, 64 patches, d=768,
  12 heads x 64, MLP 3072.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so the call has to
happen in the worker that runs this file.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_fused as KFD
from repro.kernels import ops as KOPS

T = 4
GPT = dict(b=4, d=512, h=8, kv=8, hd=64, ff=2048, l=64, page_len=8)
VIT = dict(b=8, n=64, d=768, h=12, hd=64, ff=3072)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _abstract(spec, sharding):
    """``(shape, dtype)`` leaves, in nested tuples, -> sharded shape structs."""
    if (len(spec) == 2 and isinstance(spec[0], tuple)
            and not isinstance(spec[1], tuple)):
        return jax.ShapeDtypeStruct(spec[0], spec[1], sharding=sharding)
    return tuple(_abstract(s, sharding) for s in spec)


def _compile(fn, sharding, *specs):
    """Compile ``fn`` for the described chip; returns the compiled HLO text."""
    args = _abstract(specs, sharding)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


def _w(d_in, d_out):
    """(int8 levels, f32 scale, f32 bias) crossbar operand shapes."""
    return (((d_in, d_out), jnp.int8), ((d_out,), jnp.float32),
            ((d_out,), jnp.float32))


F32, U8, I32, U32 = jnp.float32, jnp.uint8, jnp.int32, jnp.uint32


@pytest.mark.parametrize("shape", [(T, GPT["b"], 1, GPT["d"]),
                                   (T, VIT["b"], VIT["n"], VIT["d"])],
                         ids=["gpt-decode", "vit"])
def test_lif_fused_compiles(one_chip, shape):
    _compile(partial(KOPS.lif_fused, interpret=False), one_chip, (shape, F32))


@pytest.mark.parametrize("m,d_in,d_out", [
    (GPT["b"], GPT["d"], GPT["ff"]),  # GPT decode FFN up-projection
    (GPT["b"], GPT["d"], GPT["ff"] // 4),  # its column shard, model=4
    (VIT["b"] * VIT["n"], VIT["d"], VIT["ff"]),  # ViT MLP up-projection
    (VIT["b"] * VIT["n"], VIT["ff"], VIT["d"]),  # ViT MLP down-projection
], ids=["gpt-ffn", "gpt-ffn-shard", "vit-mlp-up", "vit-mlp-down"])
def test_aimc_spiking_linear_compiles(one_chip, m, d_in, d_out):
    _compile(partial(KOPS.aimc_spiking_linear, interpret=False), one_chip,
             ((T, m, d_in), F32), *_w(d_in, d_out))


@pytest.mark.parametrize("d_in", [GPT["h"] * GPT["hd"], GPT["ff"]],
                         ids=["attn-out", "ffn-down"])
def test_aimc_matmul_counts_compiles(one_chip, d_in):
    # a row-parallel shard of a GPT projection on a model=4 mesh
    _compile(partial(KOPS.aimc_matmul_counts, interpret=False), one_chip,
             ((T, GPT["b"], d_in // 4), F32), ((d_in // 4, GPT["d"]), jnp.int8))


def test_drift_requantize_compiles(one_chip):
    shp = ((GPT["d"], GPT["ff"]), F32)
    _compile(partial(KOPS.drift_requantize, t0=1.0, img_gain=4,
                     interpret=False), one_chip, shp, shp, shp, ((), F32))


@pytest.mark.parametrize("causal,n", [(False, VIT["n"]), (True, VIT["n"]),
                                      (True, 16)],
                         ids=["vit", "causal-64", "causal-16"])
def test_ssa_attention_packed_compiles(one_chip, causal, n):
    qkv = ((T, VIT["b"], VIT["h"], n, VIT["hd"]), U8)
    _compile(partial(KOPS.ssa_attention_packed, causal=causal,
                     interpret=False), one_chip, qkv, qkv, qkv, ((2,), U32))


def test_ssa_decode_dense_compiles(one_chip):
    g = GPT
    q = ((T, g["b"], g["h"], 1, g["hd"]), U8)
    kv = ((T, g["b"], g["h"], g["l"], g["hd"]), U8)
    _compile(partial(KOPS.ssa_attention_decode_packed, i_max=g["l"],
                     interpret=False), one_chip, q, kv, kv, ((g["b"], 2), U32))


def test_ssa_decode_paged_compiles(one_chip):
    g = GPT
    mp = g["l"] // g["page_len"]
    pool = ((g["b"] * mp + 2, T, g["kv"], g["page_len"], g["hd"]), U8)
    q = ((T, g["b"], g["h"], 1, g["hd"]), U8)
    _compile(partial(KOPS.ssa_attention_decode_paged_packed, i_max=g["l"],
                     interpret=False), one_chip, q, pool, pool,
             ((g["b"], mp), I32), ((g["b"], 2), U32))


def _layer_weights(heads=GPT["h"], with_tail=True):
    """Layer operands; ``heads`` < 8 is one tensor-parallel shard's share."""
    g = GPT
    qkv = (_w(g["d"], heads * g["hd"]),) * 3
    if not with_tail:  # the shard's attention stage; the tail runs outside
        return qkv
    return qkv + (_w(g["h"] * g["hd"], g["d"]), _w(g["d"], g["ff"]),
                  _w(g["ff"], g["d"]))


# (slots, heads in this program, with the FFN tail): a decode step, the
# B=1 prefill scan, and one head-parallel shard of a model=4 mesh
LAYER_CASES = [(GPT["b"], GPT["h"], True), (1, GPT["h"], True),
               (GPT["b"], GPT["h"] // 4, False)]
LAYER_IDS = ["decode", "prefill-scan", "tp-shard"]


@pytest.mark.parametrize("b,heads,with_tail", LAYER_CASES, ids=LAYER_IDS)
def test_fused_decode_layer_dense_compiles(one_chip, b, heads, with_tail):
    g = GPT
    cache = ((b, T, g["l"], heads, g["hd"]), U8)

    def step(slot_keys, s, sk, sv, pos, *ws):
        return KFD.fused_decode_layer(slot_keys, s, sk, sv, pos, *ws,
                                      hd=g["hd"], with_tail=with_tail,
                                      interpret=False)

    _compile(step, one_chip, ((b, 2), U32), ((T, b, g["d"]), F32), cache,
             cache, ((b,), I32), *_layer_weights(heads, with_tail))


@pytest.mark.parametrize("b,heads,with_tail", LAYER_CASES, ids=LAYER_IDS)
def test_fused_decode_layer_paged_compiles(one_chip, b, heads, with_tail):
    g = GPT
    mp = g["l"] // g["page_len"]
    pool = ((g["b"] * mp + 2, T, heads, g["page_len"], g["hd"]), U8)

    def step(slot_keys, s, kp, vp, tbl, pos, wpids, *ws):
        return KFD.fused_decode_layer_paged(
            slot_keys, s, kp, vp, tbl, pos, wpids, *ws, hd=g["hd"],
            with_tail=with_tail, interpret=False)

    _compile(step, one_chip, ((b, 2), U32), ((T, b, g["d"]), F32), pool, pool,
             ((b, mp), I32), ((b,), I32), ((b,), I32),
             *_layer_weights(heads, with_tail))
