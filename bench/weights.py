"""Random weights from the seed, in the parameter layout the program takes,
made on the device in one jitted call in the type they are served in.

The benchmark makes the weights itself so that its reference takes nothing
the program made. The layout (``embed``, ``final_norm``, one stacked block
per position of the layer pattern under ``scan``, leftover layers under
``rem``) is checked against the program's own ``init_params`` by shape
(``check_layout``), never by value. Scales follow the usual fan-in rule.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int, stream: str):
    """A PRNG key for one use of a seed of any size."""
    words = [int(b) for b in stream.encode()]
    state = np.random.SeedSequence([int(seed), *words]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def _normal(key, n: tuple, shape: tuple, dtype, scale: float):
    """Normal(0, scale) of shape ``n + shape``; a stacked leaf (``n`` set)
    is drawn one layer at a time so that no float32 draw of the whole
    stack is ever held."""
    draw = lambda k: jax.random.normal(k, shape, dtype) * scale
    if not n:
        return draw(key)
    return jax.lax.map(draw, jax.random.split(key, n[0]))


def _norm(m: dict, n: tuple, dtype) -> dict:
    if m["norm"] == "nonparam_ln":
        return {}
    if m["norm"] == "rmsnorm":
        return {"scale": jnp.ones(n + (m["d_model"],), dtype)}
    raise ValueError(m["norm"])


def _attn(m: dict, n: tuple, key, dtype) -> dict:
    D, H, K, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    k = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(H * hd * 2 * m["num_layers"])
    return {"wq": _normal(k[0], n, (D, H * hd), dtype, s_in),
            "wk": _normal(k[1], n, (D, K * hd), dtype, s_in),
            "wv": _normal(k[2], n, (D, K * hd), dtype, s_in),
            "wo": _normal(k[3], n, (H * hd, D), dtype, s_out)}


def _mlp(m: dict, n: tuple, key, dtype) -> dict:
    D, F = m["d_model"], m["d_ff"]
    k = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(D)
    p = {"w1": _normal(k[0], n, (D, F), dtype, s_in),
         "w2": _normal(k[1], n, (F, D), dtype,
                       1.0 / math.sqrt(F * 2 * m["num_layers"]))}
    if m["gated"]:
        p["w3"] = _normal(k[2], n, (D, F), dtype, s_in)
    return p


def _ssd(m: dict, n: tuple, key, dtype) -> dict:
    D, N, P = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    di = m["ssm_expand"] * D
    H = di // P
    conv_ch = di + 2 * N
    k = jax.random.split(key, 4)
    f32 = jnp.float32
    return {
        "in_proj": _normal(k[0], n, (D, 2 * di + 2 * N + H), dtype,
                           1.0 / math.sqrt(D)),
        "conv_w": _normal(k[1], n, (m["ssm_conv"], conv_ch), dtype,
                          1.0 / math.sqrt(m["ssm_conv"])),
        "conv_b": jnp.zeros(n + (conv_ch,), dtype),
        "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, H)), n + (H,)
                                  ).astype(f32),
        "D": jnp.ones(n + (H,), f32),
        "dt_bias": jax.random.uniform(k[2], n + (H,), f32, math.log(1e-3),
                                      math.log(1e-1)),
        "norm_scale": jnp.ones(n + (di,), dtype),
        "out_proj": _normal(k[3], n, (di, D), dtype,
                            1.0 / math.sqrt(di * 2 * m["num_layers"])),
    }


MIXERS = {"attn": _attn, "ssd": _ssd}
FFNS = {"mlp": _mlp}


def _block(m: dict, kind, n: tuple, key, dtype) -> dict:
    mixer, ffn = kind
    k1, k2 = jax.random.split(key)
    p = {"norm1": _norm(m, n, dtype), "mixer": MIXERS[mixer](m, n, k1, dtype)}
    if ffn is not None:
        p["norm2"] = _norm(m, n, dtype)
        p["ffn"] = FFNS[ffn](m, n, k2, dtype)
    return p


def _make(key, m: dict, dtype):
    pattern = [tuple(k) for k in m["block_pattern"]]
    per, n_full = len(pattern), m["num_layers"] // len(pattern)
    rem = m["num_layers"] % per
    keys = jax.random.split(key, per + rem + 2)
    D, V = m["d_model"], m["vocab_size"]
    embed = {"embedding": _normal(keys[0], (), (V, D), dtype, 0.02)}
    if not m["tie_embeddings"]:
        embed["lm_head"] = _normal(keys[1], (), (D, V), dtype, 1.0 / math.sqrt(D))
    return {"embed": embed, "final_norm": _norm(m, (), dtype),
            "scan": tuple(_block(m, pattern[j], (n_full,), keys[2 + j], dtype)
                          for j in range(per) if n_full),
            "rem": tuple(_block(m, pattern[i], (), keys[2 + per + i], dtype)
                         for i in range(rem))}


def _frozen(m: dict):
    return tuple(sorted((k, tuple(map(tuple, v)) if k == "block_pattern" else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_jit(key, frozen, dtype):
    m = {k: [list(x) for x in v] if k == "block_pattern" else v
         for k, v in frozen}
    return _make(key, m, dtype)


def make_weights(model: dict, seed: int, dtype: str):
    """All weights of ``model`` from ``seed``, in ``dtype``, on the device."""
    return _make_jit(seed_key(seed, "weights"), _frozen(model), DTYPES[dtype])


def check_layout(params, program_shapes) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of the
    program's own initialiser (``jax.eval_shape`` of it)."""
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), program_shapes)
    if jax.tree.structure(params) != jax.tree.structure(program_shapes) \
            or got != want:
        raise RuntimeError(f"weight layout differs from the program's:\n"
                           f"benchmark {got}\nprogram {want}")
