"""The plain reference: a float32 forward pass written from the published
layer equations, with no kernels, cache or batching, and imports nothing of
the program. It reads the weights the benchmark made (``bench.weights``).

The equations are the configuration's model module
(``bench/models/<config>.py``), found by name: its ``embed(p, tokens, m)``,
``layer(p, x, m, kind, quant)`` for one layer of a kind of the layer
pattern, and ``head(params, x, m, quant)`` at the rows asked for, each
making its matmuls with ``mm``. This file holds only what every
configuration shares.

Layers run one at a time (one compiled body for every layer of a kind) on
blocks of rows, so the f32 copies of one layer's weights are all that is
added to the bf16 weights already held. Every matmul runs at ``HIGHEST``
precision. ``quant="fp8"`` is the control: every weight and every matmul
input of the linear layers rounded to float8 e4m3 with a per-channel (per
row for activations) scale, the rest as above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x, w, quant):
    """``x @ w`` in float32 at ``HIGHEST``; both rounded to float8 first
    under ``quant="fp8"``."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _hashable(m: dict):
    return tuple(sorted((k, tuple(map(tuple, v)) if k == "block_pattern" else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _scan_layer(stacked, i, x, mod, mh, kind, quant):
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                     stacked)
    return mod.layer(p, x, dict(mh), kind, quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _one_layer(p, x, mod, mh, kind, quant):
    return mod.layer(p, x, dict(mh), kind, quant)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(embed, tokens, mod, mh):
    return mod.embed(embed, tokens, dict(mh))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head(params, x, rows, cols, mod, mh, quant):
    return mod.head(params, x[rows, cols], dict(mh), quant)


def logits(params, mod, m: dict, tokens: np.ndarray, rows: np.ndarray,
           cols: np.ndarray, quant=None):
    """Logits at ``(rows[i], cols[i])`` of a causal forward over ``tokens``
    ``(R, S)`` through the model module ``mod``, float32."""
    mh = _hashable(m)
    pattern = [tuple(k) for k in m["block_pattern"]]
    n_full = m["num_layers"] // len(pattern)
    x = _embed(params["embed"], jnp.asarray(tokens), mod, mh)
    for r in range(n_full):
        for j, kind in enumerate(pattern):
            x = _scan_layer(params["scan"][j], jnp.int32(r), x, mod, mh, kind,
                            quant)
    for i, p in enumerate(params["rem"]):
        x = _one_layer(p, x, mod, mh, pattern[(n_full * len(pattern) + i)
                                              % len(pattern)], quant)
    return _head(params, x, jnp.asarray(rows), jnp.asarray(cols), mod, mh,
                 quant)
