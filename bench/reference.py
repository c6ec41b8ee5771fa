"""The plain reference: a float32 forward pass written from the published
layer equations, with no kernels, cache or batching, and imports nothing of
the program. It reads the weights the benchmark made (``bench.weights``).

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

Layers run one at a time (one compiled body for every layer of a kind) on
blocks of rows, so the f32 copies of one layer's weights are all that is
added to the bf16 weights already held. Every matmul runs at ``HIGHEST``
precision. ``quant="fp8"`` is the control: every weight and every matmul
input of the linear layers rounded to float8 e4m3 with a per-channel (per
row for activations) scale, the rest as above.
"""
from __future__ import annotations

import functools
import math

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


def _mm(x, w, quant):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _norm(p, x, m):
    if m["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
            * p["scale"].astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5)


def _rope(x, theta):
    S, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attn(p, h, m, quant):
    B, S, _ = h.shape
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = _rope(_mm(h, p["wq"], quant).reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(_mm(h, p["wk"], quant).reshape(B, S, K, hd), m["rope_theta"])
    v = _mm(h, p["wv"], quant).reshape(B, S, K, hd)
    k, v = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthd->bshd", w, v, precision=HI).reshape(B, S, H * hd)
    return _mm(o, p["wo"], quant)


def _mlp(p, h, m, quant):
    a = _mm(h, p["w1"], quant)
    a = jax.nn.silu(a) * _mm(h, p["w3"], quant) if m["gated"] else jax.nn.silu(a)
    return _mm(a, p["w2"], quant)


def _ssd(p, h, m, quant):
    B, S, D = h.shape
    N, P, W = m["ssm_state"], m["ssm_head_dim"], m["ssm_conv"]
    di = m["ssm_expand"] * D
    H = di // P
    zxbcdt = _mm(h, p["in_proj"], quant)
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
    return _mm(y, p["out_proj"], quant)


MIXERS = {"attn": _attn, "ssd": _ssd}
FFNS = {"mlp": _mlp}


def _layer(p, x, m, kind, quant):
    mixer, ffn = kind
    x = x + MIXERS[mixer](p["mixer"], _norm(p["norm1"], x, m), m, quant)
    if ffn is not None:
        x = x + FFNS[ffn](p["ffn"], _norm(p["norm2"], x, m), m, quant)
    return x


def _hashable(m: dict):
    return tuple(sorted((k, tuple(map(tuple, v)) if k == "block_pattern" else v)
                        for k, v in m.items()))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _scan_layer(stacked, i, x, mh, kind, quant):
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                     stacked)
    return _layer(p, x, dict(mh), kind, quant)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _one_layer(p, x, mh, kind, quant):
    return _layer(p, x, dict(mh), kind, quant)


@jax.jit
def _embed(embed, tokens):
    return embed["embedding"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head(params, x, rows, cols, mh, quant):
    m = dict(mh)
    h = _norm(params["final_norm"], x[rows, cols], m)
    w = (params["embed"]["embedding"].T if m["tie_embeddings"]
         else params["embed"]["lm_head"])
    return _mm(h, w, quant)


def logits(params, m: dict, tokens: np.ndarray, rows: np.ndarray,
           cols: np.ndarray, quant=None):
    """Logits at ``(rows[i], cols[i])`` of a causal forward over ``tokens``
    ``(R, S)``, float32."""
    mh = _hashable(m)
    pattern = [tuple(k) for k in m["block_pattern"]]
    n_full = m["num_layers"] // len(pattern)
    x = _embed(params["embed"], jnp.asarray(tokens))
    for r in range(n_full):
        for j, kind in enumerate(pattern):
            x = _scan_layer(params["scan"][j], jnp.int32(r), x, mh, kind, quant)
    for i, p in enumerate(params["rem"]):
        x = _one_layer(p, x, mh, pattern[(n_full * len(pattern) + i)
                                         % len(pattern)], quant)
    return _head(params, x, jnp.asarray(rows), jnp.asarray(cols), mh, quant)
