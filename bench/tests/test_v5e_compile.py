"""The references compile for a described TPU v5e at the cells' sizes
(no chip needed).  The program's own kernels are compiled at these widths
by ``tests/test_tpu_compile.py``."""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import harness


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sh):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
                        tree)


def _config(name):
    return json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())


def test_lm_reference_compiles(one_chip):
    from drivers import lm_serve
    from reference import lm_spiking

    c = _config("xpikeformer-gpt-8-512")
    w = jax.eval_shape(lambda k: lm_serve.init_weights(c, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    b, p = c["check"]["sample_requests"], 320
    toks = jax.ShapeDtypeStruct((b, p), jnp.int32, sharding=one_chip)
    seeds = jax.ShapeDtypeStruct((b, p), jnp.uint32, sharding=one_chip)
    for dt in (jnp.float32, jnp.bfloat16):
        fn = jax.jit(lambda w_, t_, s_: lm_spiking.teacher_forced_logits(w_, c, t_, s_, dt))
        compiled = fn.lower(_abstract(w, one_chip), toks, seeds).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 8e9


def test_vit_reference_compiles(one_chip):
    from drivers import vit_classify
    from reference import vit_spiking

    c = _config("xpikeformer-vit-8-768")
    batch = json.loads((harness.BENCH / "traffic" / "classify.json").read_text())["batch"]
    w = jax.eval_shape(lambda k: vit_classify.init_weights(c, k),
                       jax.ShapeDtypeStruct((2,), jnp.uint32))
    x = jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.float32, sharding=one_chip)
    r = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(lambda w_, x_, r_: vit_spiking.logits(w_, c, x_, r_))
    compiled = fn.lower(_abstract(w, one_chip), x, r).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8e9
