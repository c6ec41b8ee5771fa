"""Model assembly: blocks -> stack (scan-over-layers) -> LM / encoder.

The layer stack is grouped into ``n_full`` repeats of the config's block
pattern (period p) plus ``rem`` leftover layers. The repeats run under one
``lax.scan`` whose xs are the stacked per-repeat parameters (and, when
decoding, the stacked attention caches, which the body only reads: its ys
are their new K/V rows, written into the caches after the scan; state
mixers' stacked states are carried and replaced layer by layer).
Compile cost is therefore O(period + rem) block bodies regardless of depth.

Modes:
  forward_train  — full-sequence, returns logits over all positions
  prefill        — full-sequence, returns last-position logits + cache
  decode_step    — one token with cache
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers, moe, rglru, ssm
from repro.models.config import ArchConfig

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Execution options orthogonal to the architecture."""

    use_kernels: bool = False          # Pallas kernels (TPU) vs jnp reference
    window_override: int = 0           # force sliding window (long_500k on dense)
    ring_cache: bool = False           # window-sized ring KV cache (optimized)
    remat: bool = True                 # rematerialize blocks under scan
    moe_local_dispatch: bool = False   # per-sequence MoE dispatch (perf iter 2)
    blockwise_attention: int = 0       # kv-block size for online-softmax attention (perf; 0 = off)
    gqa_expand_kv: bool = False        # expand KV to all query heads so score
                                       # tensors shard when kv_heads < model axis
    moe_expert_shard_constraint: bool = False  # pin dispatch buffers expert-sharded (perf B4)
    moe_shard_map_mesh: Any = None     # Mesh => explicit expert-parallel shard_map MoE (perf B5)
    moe_shard_map_dp: tuple = ("data",)


def _pattern_layout(cfg: ArchConfig) -> tuple[int, int]:
    p = len(cfg.block_pattern)
    return cfg.num_layers // p, cfg.num_layers % p


def effective_window(cfg: ArchConfig, kind_mixer: str, opts: ModelOptions) -> int:
    if kind_mixer == "attn_window":
        return cfg.window
    if kind_mixer == "attn" and opts.window_override > 0:
        return opts.window_override
    return 0


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def init_block(cfg: ArchConfig, kind, key, dtype):
    mixer, ffn = kind
    keys = jax.random.split(key, 4)
    p = {"norm1": layers.init_norm(cfg, keys[0], dtype)}
    if mixer in ("attn", "attn_window"):
        p["mixer"] = layers.init_attention(cfg, keys[1], dtype)
    elif mixer == "ssd":
        p["mixer"] = ssm.init_ssd(cfg, keys[1], dtype)
    elif mixer == "rglru":
        p["mixer"] = rglru.init_rglru(cfg, keys[1], dtype)
    if ffn is not None:
        p["norm2"] = layers.init_norm(cfg, keys[2], dtype)
        p["ffn"] = (moe.init_moe(cfg, keys[3], dtype) if ffn == "moe"
                    else layers.init_mlp(cfg, keys[3], dtype))
    return p


def init_block_cache(cfg: ArchConfig, kind, batch: int, cache_len: int, dtype,
                     opts: ModelOptions):
    mixer, _ = kind
    if mixer in ("attn", "attn_window"):
        w = effective_window(cfg, mixer, opts)
        L = cache_len
        if w > 0 and (opts.ring_cache or mixer == "attn_window"):
            L = min(cache_len, w)
        K, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, L, K, hd), dtype),
                "v": jnp.zeros((batch, L, K, hd), dtype)}
    if mixer == "ssd":
        return ssm.ssd_init_cache(cfg, batch, dtype)
    if mixer == "rglru":
        return rglru.rglru_init_cache(cfg, batch, dtype)
    return {}


def apply_block_full(params, x, cfg: ArchConfig, kind, opts: ModelOptions,
                     want_cache: bool, cache_len: int = 0):
    """Full-sequence block. Returns (x, aux_loss, cache_or_None)."""
    mixer, ffn = kind
    h = layers.apply_norm(params["norm1"], x, cfg)
    cache = None
    if mixer in ("attn", "attn_window"):
        w = effective_window(cfg, mixer, opts)
        out, (k, v) = layers.attention_full(
            params["mixer"], h, cfg, window=w, use_flash=opts.use_kernels,
            blockwise=opts.blockwise_attention,
            expand_kv=opts.gqa_expand_kv)
        if want_cache:
            S = x.shape[1]
            L = cache_len
            if w > 0 and (opts.ring_cache or mixer == "attn_window"):
                L = min(cache_len, w)
                # keep the last L positions, aligned to ring slots
                k = _ring_from_prefill(k, L, S)
                v = _ring_from_prefill(v, L, S)
                cache = {"k": k, "v": v}
            else:
                pad = L - S
                cache = {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                         "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))}
    elif mixer == "ssd":
        out = ssm.ssd_forward(params["mixer"], h, cfg, use_kernel=opts.use_kernels)
        if want_cache:
            cache = _ssd_cache_from_prefill(params["mixer"], h, cfg)
    elif mixer == "rglru":
        out = rglru.rglru_forward(params["mixer"], h, cfg,
                                  use_kernel=opts.use_kernels)
        if want_cache:
            cache = _rglru_cache_from_prefill(params["mixer"], h, cfg)
    else:
        raise ValueError(mixer)
    x = x + out
    aux = jnp.zeros((), jnp.float32)
    if ffn is not None:
        h2 = layers.apply_norm(params["norm2"], x, cfg)
        if ffn == "moe":
            if opts.moe_shard_map_mesh is not None:
                out2, aux = moe.apply_moe_shard_map(
                    params["ffn"], h2, cfg, opts.moe_shard_map_mesh,
                    dp_axes=opts.moe_shard_map_dp)
            else:
                out2, aux = moe.apply_moe(
                    params["ffn"], h2, cfg,
                    local_dispatch=opts.moe_local_dispatch,
                    expert_shard_constraint=opts.moe_expert_shard_constraint)
        else:
            out2 = layers.apply_mlp(params["ffn"], h2, cfg)
        x = x + out2
    return x, aux, cache


def _ring_from_prefill(k, L, S):
    """Arrange the last L of S prefill keys into ring order (slot = pos % L)."""
    if S <= L:
        return jnp.pad(k, ((0, 0), (0, L - S), (0, 0), (0, 0)))
    last = k[:, S - L:]                      # positions S-L .. S-1
    # position p sits in slot p % L; rotate accordingly
    shift = (S - L) % L
    return jnp.roll(last, shift, axis=1)


def _ssd_cache_from_prefill(mixer_params, h, cfg: ArchConfig):
    """Final SSM state after a prefill: rerun projections and take the last
    chunk state (cheap relative to the block itself; avoids threading state
    out of ssd_forward)."""
    B, S, D = h.shape
    di, H, P, N = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    zxbcdt = h @ mixer_params["in_proj"]
    _, xBC, dt = ssm._split_proj(cfg, zxbcdt)
    xBC = jax.nn.silu(ssm._causal_conv(xBC, mixer_params["conv_w"],
                                       mixer_params["conv_b"]))
    xin = xBC[..., :di].reshape(B, S, H, P).astype(jnp.float32)
    Bm = xBC[..., di: di + ssm.N_GROUPS * N].reshape(B, S, ssm.N_GROUPS, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + mixer_params["dt_bias"])
    A = -jnp.exp(mixer_params["A_log"])
    dA = dt * A                                                   # (B,S,H)
    cs = jnp.cumsum(dA, axis=1)
    decay_to_end = jnp.exp(cs[:, -1:, :] - cs)                    # (B,S,H)
    Bh = jnp.repeat(Bm, H // ssm.N_GROUPS, axis=2).astype(jnp.float32)
    state = jnp.einsum("bshn,bsh,bsh,bshp->bhpn", Bh, dt, decay_to_end, xin)
    conv_src = (h @ mixer_params["in_proj"])[..., di: di + di + 2 * ssm.N_GROUPS * N]
    conv_state = conv_src[:, S - (cfg.ssm_conv - 1):, :]
    return {"state": state, "conv": conv_state}


def _rglru_cache_from_prefill(mixer_params, h, cfg: ArchConfig):
    xw = h @ mixer_params["wx"]
    xc = rglru._causal_conv(xw, mixer_params["conv_w"], mixer_params["conv_b"])
    a, gated_in = rglru._gates(mixer_params, xc)
    hseq = rglru.rglru_scan_ref(a, gated_in)
    S = h.shape[1]
    return {"h": hseq[:, -1], "conv": xw[:, S - (cfg.rnn_conv - 1):, :]}


def _is_ring(cfg: ArchConfig, mixer: str, opts: ModelOptions, L: int) -> bool:
    """An attention cache of length ``L`` is a ring: it holds exactly the
    last ``L`` positions, position p in slot p % L."""
    w = effective_window(cfg, mixer, opts)
    return w > 0 and L <= w


def apply_block_decode(params, x, cache, pos, cfg: ArchConfig, kind,
                       opts: ModelOptions):
    """One-token block. Returns (x, new): for an attention block, which only
    reads its cache, the new token's K/V rows ``{"k", "v"}`` of (B, K, hd)
    for ``_write_decoded``; for a state mixer, its new cache, whole."""
    mixer, ffn = kind
    h = layers.apply_norm(params["norm1"], x, cfg)
    if mixer in ("attn", "attn_window"):
        if _is_ring(cfg, mixer, opts, cache["k"].shape[1]):
            out, ck, cv = layers.attention_decode_ring(
                params["mixer"], h, cache["k"], cache["v"], pos, cfg)
        else:
            # full-length cache (window masking if any)
            out, ck, cv = layers.attention_decode(
                params["mixer"], h, cache["k"], cache["v"], pos, cfg,
                window=effective_window(cfg, mixer, opts))
        new_cache = {"k": ck, "v": cv}
    elif mixer == "ssd":
        out, new_cache = ssm.ssd_step(params["mixer"], h, cache, cfg)
    elif mixer == "rglru":
        out, new_cache = rglru.rglru_step(params["mixer"], h, cache, cfg)
    else:
        raise ValueError(mixer)
    x = x + out
    if ffn is not None:
        h2 = layers.apply_norm(params["norm2"], x, cfg)
        if ffn == "moe":
            if opts.moe_shard_map_mesh is not None:
                out2, _ = moe.apply_moe_shard_map(
                    params["ffn"], h2, cfg, opts.moe_shard_map_mesh,
                    dp_axes=opts.moe_shard_map_dp)
            else:
                out2, _ = moe.apply_moe(
                    params["ffn"], h2, cfg,
                    local_dispatch=opts.moe_local_dispatch,
                    expert_shard_constraint=opts.moe_expert_shard_constraint)
        else:
            out2 = layers.apply_mlp(params["ffn"], h2, cfg)
        x = x + out2
    return x, new_cache


def _write_decoded(cache, new, pos, cfg: ArchConfig, kind,
                   opts: ModelOptions):
    """A block's cache after one decode step, from what
    ``apply_block_decode`` returned: an attention block's new rows written
    at ``pos`` (``pos % L`` in a ring), scalar or per row (B,); a state
    mixer's new cache as it is. Leaves may carry leading (repeat) axes.
    With the cache donated to the program, the write is in place."""
    mixer, _ = kind
    if mixer not in ("attn", "attn_window"):
        return new
    L = cache["k"].shape[-3]
    at = jax.lax.rem(pos, L) if _is_ring(cfg, mixer, opts, L) else pos

    def put(big, rows):              # (..., B, L, K, hd), (..., B, K, hd)
        if jnp.ndim(at) == 1:
            return big.at[..., jnp.arange(big.shape[-4]), at, :, :].set(rows)
        start = (0,) * (big.ndim - 3) + (at, 0, 0)
        return jax.lax.dynamic_update_slice(big, rows[..., None, :, :], start)
    return {n: put(cache[n], new[n]) for n in ("k", "v")}


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> Pytree:
    n_full, rem = _pattern_layout(cfg)
    p = len(cfg.block_pattern)
    kinds = cfg.layer_kinds
    k_embed, k_blocks, k_final = jax.random.split(key, 3)
    params: dict = {"embed": layers.init_embed(cfg, k_embed, dtype),
                    "final_norm": layers.init_norm(cfg, k_final, dtype)}
    bkeys = jax.random.split(k_blocks, cfg.num_layers)
    scan_params = []
    for j in range(p):
        per_repeat = [init_block(cfg, kinds[r * p + j], bkeys[r * p + j], dtype)
                      for r in range(n_full)]
        if per_repeat:
            scan_params.append(_stack_trees(per_repeat))
    params["scan"] = tuple(scan_params)
    params["rem"] = tuple(
        init_block(cfg, kinds[n_full * p + i], bkeys[n_full * p + i], dtype)
        for i in range(rem))
    return params


def init_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype,
               opts: ModelOptions) -> Pytree:
    n_full, rem = _pattern_layout(cfg)
    p = len(cfg.block_pattern)
    kinds = cfg.layer_kinds
    scan_caches = []
    for j in range(p):
        per_repeat = [init_block_cache(cfg, kinds[r * p + j], batch, cache_len,
                                       dtype, opts) for r in range(n_full)]
        if per_repeat:
            scan_caches.append(_stack_trees(per_repeat))
    return {
        "scan": tuple(scan_caches),
        "rem": tuple(init_block_cache(cfg, kinds[n_full * p + i], batch,
                                      cache_len, dtype, opts)
                     for i in range(rem)),
    }


def insert_cache_slot(cache: Pytree, one: Pytree, slot) -> Pytree:
    """Write a single-request cache (batch dim of size 1) into row ``slot``
    of a batched cache of the same cache_len/options.

    Scan caches carry a leading repeat dim — (repeat, batch, ...) leaves —
    while rem caches are (batch, ...); the batch axis is 1 resp. 0. ``slot``
    may be a traced int32, so this is jittable (the continuous-batching
    engine admits a prefilled request into a free slot without re-prefilling
    the rest of the pool).
    """
    def at_axis(axis):
        def upd(big, small):
            start = [0] * big.ndim
            start[axis] = slot
            return jax.lax.dynamic_update_slice(
                big, small.astype(big.dtype), tuple(start))
        return upd
    return {"scan": jax.tree.map(at_axis(1), cache["scan"], one["scan"]),
            "rem": jax.tree.map(at_axis(0), cache["rem"], one["rem"])}


def _sin_positions(S: int, D: int, dtype):
    pos = jnp.arange(S)[:, None].astype(jnp.float32)
    div = jnp.exp(-math.log(10_000.0) * jnp.arange(0, D, 2) / D)
    pe = jnp.zeros((S, D), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div[: (D + 1) // 2]))
    return pe.astype(dtype)


def embed_inputs(params, batch: dict, cfg: ArchConfig) -> jnp.ndarray:
    """Frontend handling: tokens / vision prefix / audio frames -> (B,S,D)."""
    if cfg.frontend == "audio":
        x = batch["frames"]
        # encoder: absolute (sinusoidal) positions stand in for the conv
        # positional embedding of the stubbed frontend
        return x + _sin_positions(x.shape[1], x.shape[2], x.dtype)[None]
    if cfg.frontend == "vision":
        tok = layers.embed_tokens(params["embed"], batch["tokens"], cfg)
        return jnp.concatenate([batch["patch_embeds"].astype(tok.dtype), tok],
                               axis=1)
    return layers.embed_tokens(params["embed"], batch["tokens"], cfg)


def apply_stack_full(params, x, cfg: ArchConfig, opts: ModelOptions,
                     want_cache: bool, cache_len: int = 0):
    n_full, rem = _pattern_layout(cfg)
    p = len(cfg.block_pattern)
    kinds = cfg.layer_kinds
    aux0 = jnp.zeros((), jnp.float32)
    cache = {"scan": (), "rem": ()}

    if n_full > 0:
        def body(carry, xs_params):
            h, aux = carry
            caches = []
            for j in range(p):
                h, aux_j, c = apply_block_full(xs_params[j], h, cfg, kinds[j],
                                               opts, want_cache, cache_len)
                aux = aux + aux_j
                caches.append(c if c is not None else {})
            return (h, aux), tuple(caches)

        if opts.remat:
            body = jax.checkpoint(body)
        (x, aux0), scan_caches = jax.lax.scan(body, (x, aux0), params["scan"])
        if want_cache:
            cache["scan"] = scan_caches

    rem_caches = []
    for i in range(rem):
        kind = kinds[n_full * p + i]
        x, aux_i, c = apply_block_full(params["rem"][i], x, cfg, kind, opts,
                                       want_cache, cache_len)
        aux0 = aux0 + aux_i
        rem_caches.append(c if c is not None else {})
    if want_cache:
        cache["rem"] = tuple(rem_caches)
    return x, aux0, (cache if want_cache else None)


def forward_hidden(params, batch: dict, cfg: ArchConfig, opts: ModelOptions):
    """Embed + stack + final norm. Returns (hidden (B,S,D), aux)."""
    x = embed_inputs(params, batch, cfg)
    x, aux, _ = apply_stack_full(params, x, cfg, opts, want_cache=False)
    x = layers.apply_norm(params["final_norm"], x, cfg)
    return x, aux


MOE_AUX_WEIGHT = 0.01


def loss_fn(params, batch: dict, cfg: ArchConfig, opts: ModelOptions):
    """Cross-entropy LM / masked-prediction loss. labels < 0 are ignored."""
    hidden, aux = forward_hidden(params, batch, cfg, opts)
    logits = layers.unembed(params["embed"], hidden, cfg).astype(jnp.float32)
    labels = batch["labels"]
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    n = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(nll) / n
    total = loss + MOE_AUX_WEIGHT * aux
    return total, {"ce_loss": loss, "aux_loss": aux,
                   "tokens": n.astype(jnp.float32)}


def prefill(params, batch: dict, cfg: ArchConfig, opts: ModelOptions,
            cache_len: int):
    """Full-sequence prefill. Returns (last-position logits (B,V), cache)."""
    x = embed_inputs(params, batch, cfg)
    x, _, cache = apply_stack_full(params, x, cfg, opts, want_cache=True,
                                   cache_len=cache_len)
    x = layers.apply_norm(params["final_norm"], x, cfg)
    last = x[:, -1]
    logits = layers.unembed(params["embed"], last[:, None], cfg)[:, 0]
    return logits.astype(jnp.float32), cache


def decode_step(params, token, pos, cache, cfg: ArchConfig,
                opts: ModelOptions):
    """One decode step. token: (B,) int32; pos: scalar int32, or (B,)
    per-row positions. Returns (logits (B,V), new cache).

    Nothing is copied out of or into ``cache`` as a whole, so a program
    that is donated it updates it in place (see the module docstring). A
    state mixer's state is carried through the scan rather than read as xs
    and written as ys: XLA copies a donated buffer that is both."""
    n_full, rem = _pattern_layout(cfg)
    p = len(cfg.block_pattern)
    kinds = cfg.layer_kinds
    x = layers.embed_tokens(params["embed"], token[:, None], cfg)

    new_cache = {"scan": (), "rem": ()}
    if n_full > 0:
        attn = [kinds[j][0] in ("attn", "attn_window") for j in range(p)]
        read = tuple(c if a else {} for c, a in zip(cache["scan"], attn))
        states = tuple({} if a else c for c, a in zip(cache["scan"], attn))

        def body(carry, xs):
            h, states = carry
            i, params_j, read_j = xs
            states, rows = list(states), []
            for j in range(p):
                c = read_j[j] if attn[j] else jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                    states[j])
                h, new = apply_block_decode(params_j[j], h, c, pos, cfg,
                                            kinds[j], opts)
                if attn[j]:
                    rows.append(new)
                else:
                    states[j] = jax.tree.map(
                        lambda a, n: jax.lax.dynamic_update_index_in_dim(
                            a, n.astype(a.dtype), i, 0), states[j], new)
                    rows.append({})
            return (h, tuple(states)), tuple(rows)

        (x, states), rows = jax.lax.scan(
            body, (x, states), (jnp.arange(n_full), params["scan"], read))
        new_cache["scan"] = tuple(
            _write_decoded(cache["scan"][j], rows[j] if attn[j] else states[j],
                           pos, cfg, kinds[j], opts) for j in range(p))

    rem_caches = []
    for i in range(rem):
        kind = kinds[n_full * p + i]
        x, c = apply_block_decode(params["rem"][i], x, cache["rem"][i], pos,
                                  cfg, kind, opts)
        rem_caches.append(_write_decoded(cache["rem"][i], c, pos, cfg, kind,
                                         opts))
    new_cache["rem"] = tuple(rem_caches)

    x = layers.apply_norm(params["final_norm"], x, cfg)
    logits = layers.unembed(params["embed"], x, cfg)[:, 0]
    return logits.astype(jnp.float32), new_cache
