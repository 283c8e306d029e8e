"""Per-kernel shape/dtype sweeps: Pallas (interpreted on CPU) vs pure-jnp oracle.

All three kernels are integer/bit-exact, so the assertion is equality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("t,b,h,n,d", [
    (1, 1, 1, 32, 32),
    (2, 2, 2, 64, 32),
    (3, 1, 2, 32, 64),
    (2, 1, 1, 128, 64),
])
@pytest.mark.parametrize("causal", [False, True])
def test_ssa_kernel_matches_ref(t, b, h, n, d, causal):
    key = jax.random.PRNGKey(n + d + t)
    ks = jax.random.split(key, 4)
    q = jax.random.bernoulli(ks[0], 0.3, (t, b, h, n, d)).astype(jnp.uint8)
    k = jax.random.bernoulli(ks[1], 0.5, (t, b, h, n, d)).astype(jnp.uint8)
    v = jax.random.bernoulli(ks[2], 0.6, (t, b, h, n, d)).astype(jnp.uint8)
    out = ops.ssa_attention_packed(q, k, v, ks[3], causal=causal)
    g = t * b * h
    rs, ra = ops.draw_comparator_prns(ks[3], (g, n, n), (g, n, d), d, n)
    exp = ref.ssa_attention_ref(
        q.reshape(g, n, d), k.reshape(g, n, d), v.reshape(g, n, d), rs, ra, causal=causal
    ).reshape(t, b, h, n, d)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int32, jnp.float32, jnp.bfloat16])
def test_ssa_kernel_input_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    q = jax.random.bernoulli(key, 0.5, (1, 1, 1, 32, 32)).astype(dtype)
    out = ops.ssa_attention_packed(q, q, q, key)
    assert out.dtype == jnp.uint8


def test_pack_unpack_roundtrip():
    key = jax.random.PRNGKey(3)
    x = jax.random.bernoulli(key, 0.5, (5, 96)).astype(jnp.uint8)
    np.testing.assert_array_equal(np.asarray(ops.unpack_bits(ops.pack_bits(x), 96)),
                                  np.asarray(x))


@pytest.mark.parametrize("t,m", [(1, 128), (4, 4096), (8, 5000), (16, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_kernel_matches_ref(t, m, dtype):
    key = jax.random.PRNGKey(t * m)
    cur = (jax.random.normal(key, (t, m)) * 1.3).astype(dtype)
    out = ops.lif_fused(cur)
    exp = ref.lif_ref(cur)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("beta,th", [(0.5, 1.0), (0.9, 0.5)])
def test_lif_kernel_params(beta, th):
    cur = jnp.full((6, 256), 0.4, jnp.float32)
    out = ops.lif_fused(cur, beta=beta, v_thresh=th)
    exp = ref.lif_ref(cur, beta=beta, v_thresh=th)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("t,b,din,dout", [
    (2, 8, 128, 128),
    (4, 17, 200, 130),
    (1, 128, 256, 384),
    (7, 3, 64, 512),
])
def test_aimc_kernel_matches_ref(t, b, din, dout):
    key = jax.random.PRNGKey(din + dout)
    ks = jax.random.split(key, 3)
    sp = jax.random.bernoulli(ks[0], 0.35, (t, b, din)).astype(jnp.float32)
    w = jax.random.randint(ks[1], (din, dout), -15, 16, jnp.int8)
    sc = jax.random.uniform(ks[2], (dout,), jnp.float32, 0.01, 0.1)
    out = ops.aimc_spiking_linear(sp, w, sc)
    exp = ref.aimc_spiking_linear_ref(sp, w, sc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@settings(deadline=None, max_examples=8)
@given(t=st.integers(1, 5), b=st.integers(1, 9),
       din=st.sampled_from([32, 100, 128]), dout=st.sampled_from([64, 128, 130]))
def test_aimc_kernel_property(t, b, din, dout):
    key = jax.random.PRNGKey(t * 1000 + b * 100 + din + dout)
    sp = jax.random.bernoulli(key, 0.4, (t, b, din)).astype(jnp.float32)
    w = jax.random.randint(key, (din, dout), -15, 16, jnp.int8)
    sc = jnp.full((dout,), 0.05, jnp.float32)
    out = ops.aimc_spiking_linear(sp, w, sc)
    exp = ref.aimc_spiking_linear_ref(sp, w, sc)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
