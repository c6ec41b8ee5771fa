"""Sharding-rule tests: divisibility on the production mesh shapes (validated
against a lightweight stand-in mesh so no 256-device runtime is needed) and a
real end-to-end jit on a 1x1 mesh exercising the same code path."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import SHAPES, InputShape
from repro.launch import sharding as SH
from repro.launch.mesh import make_smoke_mesh
from repro.models import model as M
from repro.models.config import get_config, list_archs
from repro.models.steps import TrainOptions, init_train_state, train_step

KEY = jax.random.PRNGKey(0)


class FakeMesh:
    """Duck-typed mesh: spec construction only needs .shape and .axis_names."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


PODS = [FakeMesh({"data": 16, "model": 16}),
        FakeMesh({"pod": 2, "data": 16, "model": 16})]


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    axes = axis if isinstance(axis, tuple) else (axis,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh", PODS, ids=["pod1", "pod2"])
def test_param_specs_divisible(arch, mesh):
    """Every sharded parameter dim divides evenly on the production meshes
    (this is exactly what explicit in_shardings require at lower time)."""
    cfg = get_config(arch)                      # FULL config
    policy = SH.ShardingPolicy.for_arch(cfg)
    params = jax.eval_shape(lambda: M.init_params(cfg, KEY, jnp.bfloat16))
    specs = SH.params_specs(params, mesh, policy)

    def check(path, leaf, spec):
        for d, axis in enumerate(spec):
            if axis is None:
                continue
            n = _axis_size(mesh, axis)
            assert leaf.shape[d] % n == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs,
        is_leaf=lambda x: hasattr(x, "shape"))


@pytest.mark.parametrize("arch", ["yi-9b", "mamba2-2.7b", "recurrentgemma-9b",
                                  "grok-1-314b"])
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_cache_specs_divisible(arch, shape_name):
    cfg = get_config(arch)
    mesh = PODS[0]
    shape = SHAPES[shape_name]
    policy = SH.ShardingPolicy.for_arch(cfg)
    from repro.launch.dryrun import model_options
    opts = model_options(cfg, shape)
    cache = jax.eval_shape(lambda: M.init_cache(cfg, shape.global_batch,
                                                shape.seq_len, jnp.bfloat16,
                                                opts))
    specs = SH.cache_specs(cache, cfg, shape, mesh, policy)

    def check(path, leaf, spec):
        for d, axis in enumerate(spec):
            if axis is None:
                continue
            n = _axis_size(mesh, axis)
            assert leaf.shape[d] % n == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), cache, specs,
        is_leaf=lambda x: hasattr(x, "shape"))


def test_large_archs_use_fsdp():
    assert SH.ShardingPolicy.for_arch(get_config("grok-1-314b")).fsdp
    assert SH.ShardingPolicy.for_arch(get_config("yi-9b")).fsdp
    assert not SH.ShardingPolicy.for_arch(get_config("olmo-1b")).fsdp


def test_sharded_train_step_runs_on_smoke_mesh():
    """The full sharded-jit path executes on a 1x1 mesh (CPU)."""
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    mesh = make_smoke_mesh()
    policy = SH.ShardingPolicy()
    opts = M.ModelOptions(remat=False)
    topts = TrainOptions()
    shape = InputShape("t", 64, 2, "train")
    from repro.data.pipeline import make_batch
    with mesh:
        state = init_train_state(cfg, KEY, jnp.float32, topts)
        state_sh = SH.to_named(SH.state_specs(state, mesh, policy), mesh)
        batch_sh = SH.to_named(SH.batch_specs(cfg, shape, mesh), mesh)
        state = jax.device_put(state, state_sh)
        f = functools.partial(train_step, cfg=cfg, opts=opts, topts=topts)
        step = jax.jit(f, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None))
        batch = make_batch(cfg, shape, seed=0)
        _, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))


def test_importing_dryrun_changes_no_process_state():
    """The 512-device override belongs to dryrun's main(), not its import."""
    import importlib
    import os

    import repro.launch.dryrun as dryrun
    before = os.environ.get("XLA_FLAGS")
    importlib.reload(dryrun)
    assert os.environ.get("XLA_FLAGS") == before
