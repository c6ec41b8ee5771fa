"""Step functions: train (with microbatch gradient accumulation), prefill,
decode. These are the functions the launcher jits with shardings and the
dry-run lowers."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule

Pytree = Any


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    microbatches: int = 1            # gradient-accumulation steps per batch
    opt: AdamWConfig = AdamWConfig()
    schedule_total: int = 10_000
    schedule_warmup: int = 100
    # mesh axes carrying the batch dim. When set, the microbatch reshape is
    # sharding-constrained so the *per-microbatch* batch dim stays on the
    # data axes (otherwise GSPMD may leave microbatch activations replicated
    # -- measured on grok-1 train: every score tensor carried a full
    # unsharded batch inside the accumulation loop).
    batch_axes: tuple = ()


def init_train_state(cfg: ArchConfig, key, dtype, topts: TrainOptions):
    params = M.init_params(cfg, key, dtype)
    opt_state = adamw_init(params, topts.opt)
    return {"params": params, "opt": opt_state}


def _split_microbatches(batch: dict, n: int, batch_axes=()) -> dict:
    """(B, ...) -> (n, B/n, ...) for every array with a batch dimension.

    With ``batch_axes``, constrain the result so the new per-microbatch
    batch dim (dim 1) carries the data-parallel axes and the microbatch
    dim (dim 0) is replicated (scanned over).
    """
    from jax.sharding import PartitionSpec as P

    def split(x):
        if x.ndim == 0:
            return jnp.broadcast_to(x, (n,))
        B = x.shape[0]
        assert B % n == 0, f"batch {B} not divisible by {n} microbatches"
        out = x.reshape(n, B // n, *x.shape[1:])
        if batch_axes:
            spec = P(None, batch_axes, *([None] * (out.ndim - 2)))
            out = jax.lax.with_sharding_constraint(out, spec)
        return out
    return jax.tree.map(split, batch)


def train_step(state: Pytree, batch: dict, cfg: ArchConfig,
               opts: M.ModelOptions, topts: TrainOptions):
    """One optimizer step; grads averaged over ``topts.microbatches``."""
    params = state["params"]
    grad_fn = jax.value_and_grad(M.loss_fn, has_aux=True)

    if topts.microbatches <= 1:
        (loss, metrics), grads = grad_fn(params, batch, cfg, opts)
    else:
        mb = _split_microbatches(batch, topts.microbatches,
                                 topts.batch_axes)

        def body(carry, mb_i):
            g_acc, l_acc = carry
            (l, _), g = grad_fn(params, mb_i, cfg, opts)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, l_acc + l), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), _ = jax.lax.scan(body, (g0, jnp.zeros((), jnp.float32)), mb)
        k = 1.0 / topts.microbatches
        grads = jax.tree.map(lambda g: g * k, grads)
        loss = loss_sum * k
        metrics = {}

    lr_scale = cosine_schedule(state["opt"]["step"],
                               warmup=topts.schedule_warmup,
                               total=topts.schedule_total)
    new_params, new_opt, opt_metrics = adamw_update(
        params, grads, state["opt"], topts.opt, lr_scale)
    out_metrics = {"loss": loss, **opt_metrics}
    for k_, v in (metrics or {}).items():
        out_metrics[k_] = v
    return {"params": new_params, "opt": new_opt}, out_metrics


def prefill_step(params: Pytree, batch: dict, cfg: ArchConfig,
                 opts: M.ModelOptions, cache_len: int):
    return M.prefill(params, batch, cfg, opts, cache_len)


def decode_step(params: Pytree, cache: Pytree, batch: dict, cfg: ArchConfig,
                opts: M.ModelOptions):
    """``batch["pos"]`` may be a scalar (lock-step batch) or a (B,) vector of
    per-slot positions (continuous batching). A program donated ``cache``
    writes only each slot's new K/V rows (and the state mixers' states)
    into it, in place."""
    logits, new_cache = M.decode_step(params, batch["token"], batch["pos"],
                                      cache, cfg, opts)
    return logits, new_cache


def prefill_into_slot_step(params: Pytree, cache: Pytree, batch: dict, slot,
                           cfg: ArchConfig, opts: M.ModelOptions,
                           cache_len: int):
    """Prefill ONE request (leading batch dim of 1) and insert its KV/state
    into row ``slot`` of an existing batched cache — the admission primitive
    of continuous batching: a new request joins a running pool without
    re-prefilling the other slots. A program donated ``cache`` writes only
    row ``slot``, in place. Returns (last-position logits (V,), updated
    batched cache)."""
    logits, one = M.prefill(params, batch, cfg, opts, cache_len)
    return logits[0], M.insert_cache_slot(cache, one, slot)


def _jit_as(step, fn, **jit_kwargs):
    """``jax.jit(fn)`` under ``step``'s name: the program lowers and shows
    in a device trace as ``jit_<step name>`` (a ``functools.partial``
    lowers as ``jit__unknown``)."""
    fn.__name__ = fn.__qualname__ = step.__name__
    return jax.jit(fn, **jit_kwargs)


def make_jitted_train_step(cfg: ArchConfig, opts: M.ModelOptions,
                           topts: TrainOptions, **jit_kwargs):
    def f(state, batch):
        return train_step(state, batch, cfg, opts, topts)
    return _jit_as(train_step, f, **jit_kwargs)


def make_jitted_prefill(cfg: ArchConfig, opts: M.ModelOptions, cache_len: int,
                        **jit_kwargs):
    def f(params, batch):
        return prefill_step(params, batch, cfg, opts, cache_len)
    return _jit_as(prefill_step, f, **jit_kwargs)


def make_jitted_decode(cfg: ArchConfig, opts: M.ModelOptions, **jit_kwargs):
    def f(params, cache, batch):
        return decode_step(params, cache, batch, cfg, opts)
    return _jit_as(decode_step, f, **jit_kwargs)


def make_jitted_prefill_into_slot(cfg: ArchConfig, opts: M.ModelOptions,
                                  cache_len: int, **jit_kwargs):
    def f(params, cache, batch, slot):
        return prefill_into_slot_step(params, cache, batch, slot, cfg, opts,
                                      cache_len)
    return _jit_as(prefill_into_slot_step, f, **jit_kwargs)
