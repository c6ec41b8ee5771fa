"""Shared layers: norms, RoPE, GQA attention (full/sliding, causal/bidir,
cached decode), MLPs. Pure functions over parameter dicts (pytrees)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, key, dtype):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm == "nonparam_ln":
        return {}
    raise ValueError(cfg.norm)


def apply_norm(params, x, cfg: ArchConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + 1e-6)
        return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)
    # layernorm / nonparam_ln
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
    if cfg.norm == "layernorm":
        out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S). Rotates pairs (even, odd)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xr1 = x1 * cos - x2 * sin
    xr2 = x2 * cos + x1 * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ArchConfig, key, dtype):
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(H * hd) / math.sqrt(2 * cfg.num_layers)
    return {
        "wq": (jax.random.normal(k1, (D, H * hd)) * s_in).astype(dtype),
        "wk": (jax.random.normal(k2, (D, K * hd)) * s_in).astype(dtype),
        "wv": (jax.random.normal(k3, (D, K * hd)) * s_in).astype(dtype),
        "wo": (jax.random.normal(k4, (H * hd, D)) * s_out).astype(dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def attention_blockwise(q, k, v, cfg: ArchConfig, *, window: int = 0,
                        block: int = 512):
    """Online-softmax attention scanning KV blocks (the flash-attention
    algorithm expressed in XLA ops — perf iteration for the memory term).

    Never materializes the (S, T) score matrix: one (S, block) tile lives at
    a time, and the scan body is rematerialized so the backward pass stores
    only the (m, l, acc) carries per block instead of all score tiles.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    block = min(block, T)
    assert T % block == 0, (T, block)
    nb = T // block
    qg = q.reshape(B, S, K, G, hd)
    scale = 1.0 / math.sqrt(hd)
    kb = jnp.moveaxis(k.reshape(B, nb, block, K, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block, K, hd), 1, 0)
    q_idx = jnp.arange(S)

    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        s = jnp.einsum("bskgh,btkh->bkgst", qg, kj).astype(jnp.float32) * scale
        k_idx = j * block + jnp.arange(block)
        mask = jnp.ones((S, block), bool)
        if cfg.causal:
            mask &= k_idx[None, :] <= q_idx[:, None]
        if window > 0:
            mask &= k_idx[None, :] > q_idx[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc_new = acc * alpha[..., 0][..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p.astype(vj.dtype), vj).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, S, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, S, 1), jnp.float32)
    a0 = jnp.zeros((B, K, G, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (jnp.arange(nb), kb, vb))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).astype(q.dtype)                      # (B,K,G,S,hd)
    return jnp.moveaxis(out.reshape(B, K * G, S, hd), 1, 2).reshape(B, S, H, hd)


def attention_full(params, x, cfg: ArchConfig, *, window: int = 0,
                   positions: Optional[jnp.ndarray] = None,
                   use_flash: bool = False, blockwise: int = 0,
                   expand_kv: bool = False):
    """Full-sequence attention (train / prefill). Returns (out, (k, v)).

    ``expand_kv`` repeats K/V onto every query head before the score einsum
    (mathematically identical for GQA). Rationale: when kv_heads does not
    divide the model axis (grok: 8 vs 16), GSPMD cannot shard the
    (B,K,G,S,T) score tensor on its head group dim and replicates it;
    expanding to H query heads (48 % 16 == 0) restores sharding at the cost
    of G x larger (but tiny) K/V activations.
    """
    B, S, D = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = _split_heads(x @ params["wq"], H, hd)
    k = _split_heads(x @ params["wk"], K, hd)
    v = _split_heads(x @ params["wv"], K, hd)
    if cfg.causal:  # encoders (HuBERT) use absolute embeddings upstream; rope for decoders
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if expand_kv and K < H:
        cfg = __import__("dataclasses").replace(cfg, num_kv_heads=H)
        G = H // K
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        K = H

    if use_flash:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=cfg.causal, window=window)
    elif blockwise > 0:
        out = attention_blockwise(q, k, v, cfg, window=window, block=blockwise)
    else:
        G = H // K
        qg = q.reshape(B, S, K, G, hd)
        scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) / math.sqrt(hd)
        srange = jnp.arange(S)
        mask = jnp.ones((S, S), dtype=bool)
        if cfg.causal:
            mask &= srange[None, :] <= srange[:, None]
        if window > 0:
            mask &= srange[None, :] > srange[:, None] - window
        scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        out = jnp.einsum("bkgst,btkh->bskgh", w, v).reshape(B, S, H * hd)
    return out.reshape(B, S, H * hd) @ params["wo"], (k, v)


def _attend_cached_and_new(q, k_new, v_new, cache_k, cache_v, mask, dtype):
    """One query token per row against the cached keys where ``mask``
    (B, T) holds and against its own new key, with one softmax over both.
    The same arithmetic as attending over a cache that already holds the
    new row, without writing it: q (B, 1, H, hd); k_new, v_new (B, K, hd);
    cache_[kv] (B, T, K, hd). Returns (B, 1, H * hd) in ``dtype``."""
    B, _, H, hd = q.shape
    K = cache_k.shape[2]
    G = H // K
    qg = q.reshape(B, 1, K, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, cache_k) / math.sqrt(hd)
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    own = jnp.einsum("bskgh,bkh->bkgs", qg, k_new)[..., None] / math.sqrt(hd)
    w = jax.nn.softmax(jnp.concatenate([scores, own], axis=-1)
                       .astype(jnp.float32), axis=-1).astype(dtype)
    out = (jnp.einsum("bkgst,btkh->bskgh", w[..., :-1], cache_v,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgs,bkh->bskgh", w[..., -1], v_new,
                        preferred_element_type=jnp.float32))
    return out.astype(dtype).reshape(B, 1, H * hd)


def _decode_qkv(params, x, pos, cfg: ArchConfig, cache_dtype):
    """The new token's query (B, 1, H, hd) and its K/V rows (B, K, hd) in
    the cache's dtype, RoPE'd at ``pos`` (scalar, or (B,) per row); and
    the positions as (B, 1)."""
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _split_heads(x @ params["wq"], H, hd)
    k = _split_heads(x @ params["wk"], K, hd)
    v = _split_heads(x @ params["wv"], K, hd)
    posb = pos[:, None] if jnp.ndim(pos) == 1 else jnp.full((B, 1), pos)
    q = rope(q, posb, cfg.rope_theta)
    k = rope(k, posb, cfg.rope_theta)
    return q, k[:, 0].astype(cache_dtype), v[:, 0].astype(cache_dtype), posb


def attention_decode(params, x, cache_k, cache_v, pos, cfg: ArchConfig, *,
                     window: int = 0):
    """One-token decode. x: (B, 1, D); cache_[kv]: (B, S_max, K, hd);
    pos: scalar int32 — the new token's position, or (B,) int32 for per-row
    positions (continuous batching: each slot decodes at its own depth).

    The cache is only read: the new token attends to the positions before
    ``pos`` (within ``window``) and to itself. Returns (out, new_k, new_v),
    the new rows (B, K, hd) that the caller writes at ``pos``."""
    q, k, v, posb = _decode_qkv(params, x, pos, cfg, cache_k.dtype)
    trange = jnp.arange(cache_k.shape[1])
    mask = trange[None, :] < posb                         # (B, S_max)
    if window > 0:
        mask &= trange[None, :] > posb - window
    out = _attend_cached_and_new(q, k, v, cache_k, cache_v, mask, x.dtype)
    return out @ params["wo"], k, v


def attention_decode_ring(params, x, cache_k, cache_v, pos, cfg: ArchConfig):
    """One-token decode against a ring (window-sized) KV cache of length L.

    Slot = position % L. The ring holds the last L positions before
    ``pos``; the new token attends to every slot already written except
    slot ``pos % L``, whose position ``pos - L`` its own row replaces, and
    to itself. Keys are RoPE'd at their absolute position at write time,
    so relative phases are correct. ``pos`` may be scalar or (B,) for
    per-row decode depths (continuous batching). Returns (out, new_k,
    new_v), the new rows (B, K, hd) that the caller writes at ``pos % L``.
    """
    q, k, v, posb = _decode_qkv(params, x, pos, cfg, cache_k.dtype)
    L = cache_k.shape[1]
    slots = jnp.arange(L)[None, :]
    mask = (slots < posb) & (slots != jax.lax.rem(posb, L))   # (B, L)
    out = _attend_cached_and_new(q, k, v, cache_k, cache_v, mask, x.dtype)
    return out @ params["wo"], k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key, dtype, d_ff: Optional[int] = None):
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(F) / math.sqrt(2 * cfg.num_layers)
    p = {"w1": (jax.random.normal(k1, (D, F)) * s_in).astype(dtype),
         "w2": (jax.random.normal(k2, (F, D)) * s_out).astype(dtype)}
    if cfg.gated:
        p["w3"] = (jax.random.normal(k3, (D, F)) * s_in).astype(dtype)
    return p


def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


def apply_mlp(params, x, cfg: ArchConfig):
    h = _act(x @ params["w1"], cfg.activation)
    if cfg.gated:
        h = h * (x @ params["w3"])
    return h @ params["w2"]


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def init_embed(cfg: ArchConfig, key, dtype):
    k1, k2 = jax.random.split(key)
    p = {"embedding": (jax.random.normal(k1, (cfg.vocab_size, cfg.d_model))
                       * 0.02).astype(dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(k2, (cfg.d_model, cfg.vocab_size))
                        / math.sqrt(cfg.d_model)).astype(dtype)
    return p


def embed_tokens(params, tokens, cfg: ArchConfig):
    return params["embedding"][tokens]


def unembed(params, x, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return x @ params["embedding"].T
    return x @ params["lm_head"]
