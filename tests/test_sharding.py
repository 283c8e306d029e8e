"""Sharding rule tests against the abstract production mesh (no devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, get_config
from repro.models import transformer as T
from repro.optim import adamw as A
from repro.parallel import sharding as SH

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_spec_divisibility_fallback():
    # qwen: 40 heads don't divide 16 -> replicated; d_ff 27648 does -> model
    s = SH.spec_for(("embed", "heads", "head_dim"), (5120, 40, 128), MESH)
    assert tuple(s) in (("data",), ("data", None), ("data", None, None))
    s = SH.spec_for(("embed", "ffn"), (5120, 27648), MESH)
    assert tuple(s) == ("data", "model")


def test_spec_axis_used_once():
    # both dims want "model": only the first gets it
    s = SH.spec_for(("vocab", "ffn"), (32000, 4864), MESH)
    assert tuple(s) == ("model",)


def test_fsdp_gate():
    s = SH.spec_for(("embed", "ffn"), (4096, 12800), MESH, fsdp=False)
    assert tuple(s) == (None, "model")


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_all_archs(arch):
    """Every leaf gets a valid spec whose sharded dims divide the axis."""
    cfg = get_config(arch)
    specs = SH.param_pspecs(cfg, MESH)
    schema = T.model_schema(cfg)
    sizes = SH.axis_sizes(MESH)
    flat_s = jax.tree.leaves(specs)
    flat_d = jax.tree.leaves(schema, is_leaf=lambda x: hasattr(x, "axes"))
    assert len(flat_s) == len(flat_d)
    for spec, d in zip(flat_s, flat_d):
        for dim, entry in zip(d.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = int(np.prod([sizes[a] for a in axes]))
            assert dim % prod == 0, f"{arch}: {d.shape} {spec}"


def test_moe_expert_specs_match_shardmap_contract():
    cfg = get_config("arctic-480b")
    specs = SH.param_pspecs(cfg, MESH)
    wi = specs["periods"]["blk0"]["moe"]["wi"]
    wo = specs["periods"]["blk0"]["moe"]["wo"]
    assert tuple(wi) == (None, "model", None, "data")  # [layers, E, d, f]
    assert tuple(wo) == (None, "model", "data")  # [layers, E, f, d] (d trimmed)


def test_batch_and_cache_specs():
    assert SH.batch_pspec(MESH3, 256) == ("pod", "data")
    assert SH.batch_pspec(MESH3, 1) is None
    cfg = get_config("qwen2.5-32b")
    cs = SH.cache_pspecs(cfg, MESH, 128, 32768)
    kspec = cs["periods"]["blk0"]["k"]
    assert tuple(kspec)[:3] == (None, "data", "model")  # [layers, B, S, ...]


def test_opt_state_specs_parallel():
    cfg = get_config("arctic-480b")
    pspecs = SH.param_pspecs(cfg, MESH)
    aparams = T.abstract_params(cfg)
    opt = A.AdamWConfig(state_dtype="int8")
    ospecs = A.opt_state_pspecs(pspecs, aparams, opt)
    wi_m = ospecs["m"]["periods"]["blk0"]["moe"]["wi"]
    assert set(wi_m) == {"q", "scale"}


@pytest.mark.parametrize("mesh", [MESH, MESH3])
def test_make_pctx(mesh):
    from repro.configs.base import ParallelConfig

    pctx = SH.make_pctx(mesh, ParallelConfig())
    assert pctx.tp_axis == "model"
    assert pctx.fsdp_axis == "data"
    assert pctx.tp_size == 16
