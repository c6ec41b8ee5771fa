"""The pre-norm decoder that the model modules share: weights in the
program's layout and the reference's equations for its layer kinds.

- Dense decoder (OLMo, arXiv:2402.00838): pre-norm blocks, non-parametric
  layer norm (eps 1e-5) or RMSNorm (eps 1e-6), rotary embeddings on
  half-split pairs (theta from the config), causal softmax attention,
  SwiGLU MLP, tied or separate output head.
- Mamba-2 SSD block (arXiv:2405.21060): input projection to (z, x, B, C,
  dt), depthwise causal conv of width ``ssm_conv`` with bias and SiLU,
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the selective scan
  in its quadratic (masked-attention) form
  ``y_t = sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s + D x_t`` with
  ``cs`` the running sum of ``dt * A``, gated by SiLU(z), RMSNorm with a
  scale, output projection.

A model module that needs a layer kind this file lacks extends the tables:
``make(key, m, dtype, mixers={**MIXER_WEIGHTS, ...})`` and
``layer(p, x, m, kind, quant, mixers={**MIXERS, ...})``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import HI, mm
from bench.weights import normal


# -- weights: the program's layout, the usual fan-in scales -----------------

def norm_weights(m: dict, n: tuple, dtype) -> dict:
    if m["norm"] == "nonparam_ln":
        return {}
    if m["norm"] == "rmsnorm":
        return {"scale": jnp.ones(n + (m["d_model"],), dtype)}
    raise ValueError(m["norm"])


def attn_weights(m: dict, n: tuple, key, dtype) -> dict:
    D, H, K, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    k = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(H * hd * 2 * m["num_layers"])
    return {"wq": normal(k[0], n, (D, H * hd), dtype, s_in),
            "wk": normal(k[1], n, (D, K * hd), dtype, s_in),
            "wv": normal(k[2], n, (D, K * hd), dtype, s_in),
            "wo": normal(k[3], n, (H * hd, D), dtype, s_out)}


def mlp_weights(m: dict, n: tuple, key, dtype) -> dict:
    D, F = m["d_model"], m["d_ff"]
    k = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(D)
    p = {"w1": normal(k[0], n, (D, F), dtype, s_in),
         "w2": normal(k[1], n, (F, D), dtype,
                      1.0 / math.sqrt(F * 2 * m["num_layers"]))}
    if m["gated"]:
        p["w3"] = normal(k[2], n, (D, F), dtype, s_in)
    return p


def ssd_weights(m: dict, n: tuple, key, dtype) -> dict:
    D, N, P = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    di = m["ssm_expand"] * D
    H = di // P
    conv_ch = di + 2 * N
    k = jax.random.split(key, 4)
    f32 = jnp.float32
    return {
        "in_proj": normal(k[0], n, (D, 2 * di + 2 * N + H), dtype,
                          1.0 / math.sqrt(D)),
        "conv_w": normal(k[1], n, (m["ssm_conv"], conv_ch), dtype,
                         1.0 / math.sqrt(m["ssm_conv"])),
        "conv_b": jnp.zeros(n + (conv_ch,), dtype),
        "A_log": jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, H)), n + (H,)
                                  ).astype(f32),
        "D": jnp.ones(n + (H,), f32),
        "dt_bias": jax.random.uniform(k[2], n + (H,), f32, math.log(1e-3),
                                      math.log(1e-1)),
        "norm_scale": jnp.ones(n + (di,), dtype),
        "out_proj": normal(k[3], n, (di, D), dtype,
                           1.0 / math.sqrt(di * 2 * m["num_layers"])),
    }


MIXER_WEIGHTS = {"attn": attn_weights, "ssd": ssd_weights}
FFN_WEIGHTS = {"mlp": mlp_weights}


def _block(m: dict, kind, n: tuple, key, dtype, mixers, ffns) -> dict:
    mixer, ffn = kind
    k1, k2 = jax.random.split(key)
    p = {"norm1": norm_weights(m, n, dtype), "mixer": mixers[mixer](m, n, k1, dtype)}
    if ffn is not None:
        p["norm2"] = norm_weights(m, n, dtype)
        p["ffn"] = ffns[ffn](m, n, k2, dtype)
    return p


def make(key, m: dict, dtype, mixers=MIXER_WEIGHTS, ffns=FFN_WEIGHTS):
    """Every weight: ``embed``, ``final_norm``, one stacked block per
    position of the layer pattern under ``scan``, leftover layers under
    ``rem``."""
    pattern = [tuple(k) for k in m["block_pattern"]]
    per, n_full = len(pattern), m["num_layers"] // len(pattern)
    rem = m["num_layers"] % per
    keys = jax.random.split(key, per + rem + 2)
    D, V = m["d_model"], m["vocab_size"]
    embed = {"embedding": normal(keys[0], (), (V, D), dtype, 0.02)}
    if not m["tie_embeddings"]:
        embed["lm_head"] = normal(keys[1], (), (D, V), dtype, 1.0 / math.sqrt(D))
    return {"embed": embed, "final_norm": norm_weights(m, (), dtype),
            "scan": tuple(_block(m, pattern[j], (n_full,), keys[2 + j], dtype,
                                 mixers, ffns)
                          for j in range(per) if n_full),
            "rem": tuple(_block(m, pattern[i], (), keys[2 + per + i], dtype,
                                mixers, ffns)
                         for i in range(rem))}


# -- the reference's equations, float32 -------------------------------------

def norm(p, x, m):
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * p["scale"].astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attn(p, h, m, quant):
    B, S, _ = h.shape
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = rope(mm(h, p["wq"], quant).reshape(B, S, H, hd), m["rope_theta"])
    k = rope(mm(h, p["wk"], quant).reshape(B, S, K, hd), m["rope_theta"])
    v = mm(h, p["wv"], quant).reshape(B, S, K, hd)
    k, v = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthd->bshd", w, v, precision=HI).reshape(B, S, H * hd)
    return mm(o, p["wo"], quant)


def mlp(p, h, m, quant):
    a = mm(h, p["w1"], quant)
    a = jax.nn.silu(a) * mm(h, p["w3"], quant) if m["gated"] else jax.nn.silu(a)
    return mm(a, p["w2"], quant)


def ssd(p, h, m, quant):
    B, S, D = h.shape
    N, P, W = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    di = m["ssm_expand"] * D
    H = di // P
    zxbcdt = mm(h, p["in_proj"], quant)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                  zxbcdt[..., 2 * di + 2 * N:])
    cw = p["conv_w"].astype(jnp.float32)
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + S] * cw[i] for i in range(W)) \
        + p["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    x = xbc[..., :di].reshape(B, S, H, P)
    Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (B,S,H)
    cs = jnp.cumsum(dt * -jnp.exp(p["A_log"]), axis=1)            # (B,S,H)
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    diff = cs[:, :, None, :] - cs[:, None, :, :]                  # (B,t,s,H)
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = jnp.einsum("btn,bsn->bts", Cm, Bm, precision=HI)
    mix = cb[..., None] * decay * dt[:, None, :, :]               # (B,t,s,H)
    y = jnp.einsum("btsh,bshp->bthp", mix, x, precision=HI)
    y = (y + p["D"][None, None, :, None] * x).reshape(B, S, di)
    y = y * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6) \
        * p["norm_scale"].astype(jnp.float32)
    return mm(y, p["out_proj"], quant)


MIXERS = {"attn": attn, "ssd": ssd}
FFNS = {"mlp": mlp}


def embed(p, tokens, m):
    return p["embedding"][tokens].astype(jnp.float32)


def layer(p, x, m, kind, quant, mixers=MIXERS, ffns=FFNS):
    mixer, ffn = kind
    x = x + mixers[mixer](p["mixer"], norm(p["norm1"], x, m), m, quant)
    if ffn is not None:
        x = x + ffns[ffn](p["ffn"], norm(p["norm2"], x, m), m, quant)
    return x


def head(params, x, m, quant):
    h = norm(params["final_norm"], x, m)
    w = (params["embed"]["embedding"].T if m["tie_embeddings"]
         else params["embed"]["lm_head"])
    return mm(h, w, quant)
