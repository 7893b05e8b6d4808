"""Sharding rules: every (arch x mesh) assignment must be divisible and
well-formed — no compile needed, so this covers all 10 archs cheaply."""

import numpy as np
import pytest

# build tiny fake meshes out of the single CPU device via AbstractMesh
import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, shapes_for
from repro.launch.steps import cell_model_config
from repro.models import build_model
from repro.parallel.sharding import ShardingRules


def _mesh(multi_pod=False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return AbstractMesh(shape, axes)


def _check_spec_divides(shape, spec, mesh):
    for dim, axes in enumerate(spec):
        if axes is None:
            continue
        axes = axes if isinstance(axes, tuple) else (axes,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        assert shape[dim] % size == 0, \
            f"dim {dim} of {shape} not divisible by {axes}={size}"


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("multi_pod", [False, True])
def test_param_specs_divide(arch, multi_pod):
    cfg = get_config(arch)
    mesh = _mesh(multi_pod)
    rules = ShardingRules(mesh=mesh, cfg=cfg)
    aparams = build_model(cfg).abstract_params()
    pspecs = rules.params_pspecs(aparams)

    leaves_and_specs = zip(
        jax.tree_util.tree_leaves(aparams),
        jax.tree_util.tree_leaves(pspecs,
                                  is_leaf=lambda x: isinstance(x, P)))
    n_sharded = 0
    for leaf, spec in leaves_and_specs:
        _check_spec_divides(leaf.shape, spec, mesh)
        if any(s is not None for s in spec):
            n_sharded += 1
    assert n_sharded > 0, "nothing sharded at all"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cache_specs_divide(arch):
    cfg = get_config(arch)
    mesh = _mesh()
    for shape in shapes_for(cfg):
        if not shape.is_decode:
            continue
        mcfg = cell_model_config(cfg, shape)
        rules = ShardingRules(mesh=mesh, cfg=mcfg)
        model = build_model(mcfg)
        acache = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        cspecs = rules.cache_pspecs(acache)
        for leaf, spec in zip(
                jax.tree_util.tree_leaves(acache),
                jax.tree_util.tree_leaves(
                    cspecs, is_leaf=lambda x: isinstance(x, P))):
            _check_spec_divides(leaf.shape, spec, mesh)


def test_batch_spec_falls_back():
    cfg = get_config("llama3_8b")
    rules = ShardingRules(mesh=_mesh(), cfg=cfg)
    assert rules.batch_spec(256) == ("data",)
    assert rules.batch_spec(1) is None          # long_500k: unshardable
    assert rules.batch_spec(17) is None


def test_attention_fallback_when_heads_dont_divide():
    """qwen1.5 (40 heads) and paligemma (8 heads) cannot TP 16 ways:
    attention weights must fall back to FSDP-only."""
    mesh = _mesh()
    for arch, heads_ok in [("qwen15_32b", False), ("paligemma_3b", False),
                           ("llama3_8b", True)]:
        cfg = get_config(arch)
        rules = ShardingRules(mesh=mesh, cfg=cfg)
        spec = rules.param_spec("units/layer0/attn/wq", (1, 4096, 4096))
        if heads_ok:
            assert "model" in str(spec)
        else:
            assert "model" not in str(spec)
