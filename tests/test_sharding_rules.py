"""Sharding rules: totality (never a non-divisible spec) + intent.

These tests use AbstractMesh — no devices needed, pure spec arithmetic.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.distributed import sharding as shd
from repro.models import build_model

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _check_divisible(specs, tree, mesh):
    sizes = _axis_sizes(mesh)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    flat_l = jax.tree.leaves(tree)
    assert len(flat_s) == len(flat_l)
    for spec, leaf in zip(flat_s, flat_l):
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            div = 1
            for a in axes:
                div *= sizes[a]
            assert leaf.shape[d] % div == 0, (spec, leaf.shape)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["1pod", "2pod"])
def test_param_specs_always_divisible(arch, mesh):
    model = build_model(ARCHS[arch])
    params = model.abstract_params()
    _check_divisible(shd.param_specs(params, mesh), params, mesh)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_opt_specs_always_divisible(arch):
    model = build_model(ARCHS[arch])
    params = model.abstract_params()
    _check_divisible(shd.opt_specs(params, mesh=MESH_2POD), params, MESH_2POD)


def test_embedding_vocab_sharded():
    model = build_model(ARCHS["qwen2-7b"])
    params = model.abstract_params()
    specs = shd.param_specs(params, MESH_1POD)
    assert specs["embed"]["table"][0] == "model"


def test_expert_dim_sharded():
    model = build_model(ARCHS["olmoe-1b-7b"])
    params = model.abstract_params()
    specs = shd.param_specs(params, MESH_1POD)
    seg = specs["decoder"]["seg0"]["sub0"]["mlp"]
    # (rep, E, D, F): expert dim over model
    assert seg["gate"][1] == "model"
    assert seg["down"][1] == "model"


def test_megatron_pairing_dense():
    model = build_model(ARCHS["qwen2-7b"])
    params = model.abstract_params()
    specs = shd.param_specs(params, MESH_1POD)
    sub = specs["decoder"]["seg0"]["sub0"]
    assert sub["mixer"]["wq"]["w"][-1] == "model"     # column
    assert sub["mixer"]["wo"]["w"][-2] == "model"     # row
    assert sub["mlp"]["gate"]["w"][-1] == "model"
    assert sub["mlp"]["down"]["w"][-2] == "model"


def test_opt_specs_add_dp_axis():
    model = build_model(ARCHS["jamba-v0.1-52b"])
    params = model.abstract_params()
    pspecs = shd.param_specs(params, MESH_2POD)
    ospecs = shd.opt_specs(params, MESH_2POD)
    flat_p = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    flat_o = jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P))
    flat_l = jax.tree.leaves(params)
    improved = 0
    for ps, os_, leaf in zip(flat_p, flat_o, flat_l):
        ents_p = [e for e in ps if e is not None]
        ents_o = [e for e in os_ if e is not None]
        assert len(ents_o) >= len(ents_p)
        if leaf.size > 1e6:
            improved += int(len(ents_o) > len(ents_p))
    assert improved > 10  # ZeRO-1 sharding actually engages on big leaves


def test_batch_specs_handle_tiny_batch():
    batch = {"tokens": jax.ShapeDtypeStruct((1, 524288), jnp.int32)}
    specs = shd.batch_specs(batch, MESH_2POD)
    # batch of 1: unsharded batch dim; seq over model
    assert specs["tokens"][0] is None
    assert specs["tokens"][1] == "model"


def test_cache_specs_shard_seq_over_model():
    cache = {"k": jax.ShapeDtypeStruct((128, 32768, 4, 128), jnp.bfloat16)}
    specs = shd.cache_specs(cache, MESH_1POD)
    assert specs["k"][0] == "data"
    assert specs["k"][1] == "model"
