"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed next to the CPU backend, and it compiles for a
chip that is described rather than attached. Each kernel of the served
architectures is compiled at its real widths with ``interpret=False``, and
the compiled program must contain the Pallas kernel (``tpu_custom_call``).
A full-width OLMo-1B serving step must fit one chip's HBM.

The topology is described inside a module-scoped fixture, never at import
time: only one process may hold the TPU library, and every test worker
imports this file. Nothing here runs anything; results and times come from
``chip_smoke.py`` on the chip.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.models import model as M
from repro.models import steps as ST
from repro.models.config import get_config

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _flash(arch, S, window=0):
    cfg = get_config(arch)
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    fn = functools.partial(flash_attention, causal=True, window=window,
                           interpret=False)
    return fn, [((1, S, H, hd), jnp.bfloat16), ((1, S, K, hd), jnp.bfloat16),
                ((1, S, K, hd), jnp.bfloat16)]


def _ssd(S):
    cfg = get_config("mamba2-2.7b")
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    fn = functools.partial(ssd_scan, chunk=cfg.ssm_chunk, interpret=False)
    return fn, [((1, S, H, P), jnp.bfloat16), ((1, S, H), jnp.float32),
                ((H,), jnp.float32), ((1, S, 1, N), jnp.bfloat16),
                ((1, S, 1, N), jnp.bfloat16)]


def _rglru(S):
    W = get_config("recurrentgemma-9b").rnn_width
    fn = functools.partial(rglru_scan, interpret=False)
    return fn, [((1, S, W), jnp.float32)] * 2


@pytest.mark.parametrize("case", [
    ("olmo-1b S=32", lambda: _flash("olmo-1b", 32)),
    ("olmo-1b S=2048", lambda: _flash("olmo-1b", 2048)),
    ("olmo-1b S=200 off the block grid", lambda: _flash("olmo-1b", 200)),
    ("recurrentgemma-9b MQA window=2048", lambda: _flash(
        "recurrentgemma-9b", 4096, get_config("recurrentgemma-9b").window)),
    ("mamba2-2.7b ssd_scan", lambda: _ssd(512)),
    ("recurrentgemma-9b rglru_scan W=4096", lambda: _rglru(512)),
    ("recurrentgemma-9b rglru_scan W=4096 S=200", lambda: _rglru(200)),
], ids=lambda c: c[0])
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = case[1]()
    compiled = _compile(fn, *(_sds(one_chip, s, d) for s, d in shapes))
    assert "tpu_custom_call" in compiled.as_text()


def _olmo_serving_shapes(one_chip, opts, cache_len=128):
    cfg = get_config("olmo-1b")
    key = jax.random.PRNGKey(0)
    place = functools.partial(jax.tree.map,
                              lambda s: _sds(one_chip, s.shape, s.dtype))
    params = place(jax.eval_shape(
        lambda: M.init_params(cfg, key, jnp.bfloat16)))
    cache = place(jax.eval_shape(
        lambda: M.init_cache(cfg, 8, cache_len, jnp.bfloat16, opts)))
    return cfg, params, cache


def test_olmo_1b_decode_step_fits_one_chip(one_chip):
    """The continuous engine's decode step at full width (bf16, 8 slots x
    128 positions) compiles for one v5e and fits its HBM."""
    opts = M.ModelOptions(remat=False)
    cfg, params, cache = _olmo_serving_shapes(one_chip, opts)
    batch = {"token": _sds(one_chip, (8,), jnp.int32),
             "pos": _sds(one_chip, (8,), jnp.int32)}
    compiled = _compile(functools.partial(ST.decode_step, cfg=cfg, opts=opts),
                        params, cache, batch)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 2e9 < mem.argument_size_in_bytes and used < HBM_BYTES, mem


@pytest.mark.parametrize("prompt_len,cache_len", [(32, 128), (200, 256)],
                         ids=["S=32", "S=200"])
def test_olmo_1b_served_prefill_carries_flash_kernel(one_chip, monkeypatch,
                                                     prompt_len, cache_len):
    """The engine's prefill-into-slot at full width, with the kernels the
    engine turns on for a TPU backend, contains the compiled flash kernel:
    at the frame prompt, and at a prompt off the kernel's 128-row block grid.
    The kernels' backend check sees this process's CPU, so the test tells
    them they are compiled for the chip."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    opts = M.ModelOptions(remat=False, use_kernels=True)
    cfg, params, cache = _olmo_serving_shapes(one_chip, opts, cache_len)
    compiled = _compile(
        functools.partial(ST.prefill_into_slot_step, cfg=cfg, opts=opts,
                          cache_len=cache_len),
        params, cache,
        {"tokens": _sds(one_chip, (1, prompt_len), jnp.int32)},
        _sds(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
