"""The decode step writes each attention layer's new K/V rows into a cache
its layers only read: its logits and new cache equal those of a plain
scatter-then-attend decode, kept here as the reference, for full, windowed
and ring caches, scalar and per-row positions, and a pattern in which a
ring cache and a state mixer (RG-LRU) share one layer scan."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers, rglru, ssm
from repro.models import model as M
from repro.models.config import get_config

ROWS = 4


def _per_layer(tree, n_full, p):
    """The stacked scan entries and the rem entries of a params or cache
    tree, as one entry per layer in layer order."""
    scan = [jax.tree.map(lambda a: a[r], tree["scan"][j])
            for r in range(n_full) for j in range(p)]
    return scan + list(tree["rem"])


def _restack(per_layer, n_full, p):
    scan = tuple(jax.tree.map(lambda *xs: jnp.stack(xs),
                              *per_layer[j:n_full * p:p])
                 for j in range(p)) if n_full else ()
    return {"scan": scan, "rem": tuple(per_layer[n_full * p:])}


def _attend_after_write(mp, h, c, pos, cfg, window, ring):
    """Write the new token's K/V into the cache first, then attend over the
    cache: positions up to ``pos`` (within ``window``), or every ring slot
    already written."""
    B = h.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    posb = jnp.broadcast_to(pos, (B,))[:, None]
    q = layers.rope((h @ mp["wq"]).reshape(B, 1, H, hd), posb, cfg.rope_theta)
    k = layers.rope((h @ mp["wk"]).reshape(B, 1, K, hd), posb, cfg.rope_theta)
    v = (h @ mp["wv"]).reshape(B, 1, K, hd)
    L = c["k"].shape[1]
    at = posb[:, 0] % L if ring else posb[:, 0]
    rows = jnp.arange(B)
    ck = c["k"].at[rows, at].set(k[:, 0])
    cv = c["v"].at[rows, at].set(v[:, 0])
    t = jnp.arange(L)[None, :]
    mask = t <= posb
    if window > 0 and not ring:
        mask &= t > posb - window
    qg = q.reshape(B, 1, K, H // K, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, ck) / math.sqrt(hd)
    scores = jnp.where(mask[:, None, None, None, :], scores, layers.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", w, cv).reshape(B, 1, H * hd)
    return out @ mp["wo"], {"k": ck, "v": cv}


def _decode_reference(params, token, pos, cache, cfg, opts):
    """One decode step, layer by layer, scattering before attending."""
    n_full, _ = M._pattern_layout(cfg)
    p = len(cfg.block_pattern)
    lp = _per_layer(params, n_full, p)
    lc = _per_layer(cache, n_full, p)
    x = layers.embed_tokens(params["embed"], token[:, None], cfg)
    for i, (mixer, ffn) in enumerate(cfg.layer_kinds):
        h = layers.apply_norm(lp[i]["norm1"], x, cfg)
        if mixer in ("attn", "attn_window"):
            w = M.effective_window(cfg, mixer, opts)
            ring = w > 0 and lc[i]["k"].shape[1] <= w
            out, lc[i] = _attend_after_write(lp[i]["mixer"], h, lc[i], pos,
                                             cfg, w, ring)
        elif mixer == "rglru":
            out, lc[i] = rglru.rglru_step(lp[i]["mixer"], h, lc[i], cfg)
        else:
            out, lc[i] = ssm.ssd_step(lp[i]["mixer"], h, lc[i], cfg)
        x = x + out
        if ffn is not None:
            x = x + layers.apply_mlp(
                lp[i]["ffn"], layers.apply_norm(lp[i]["norm2"], x, cfg), cfg)
    x = layers.apply_norm(params["final_norm"], x, cfg)
    logits = layers.unembed(params["embed"], x, cfg)[:, 0]
    return logits, _restack(lc, n_full, p)


# (arch, options, cache_len): OLMo's full cache, the same cache masked to
# a window, and a ring of the window's length; RecurrentGemma's windowed
# attention (window 64 at the reduced size) in a cache shorter than the
# window and in a ring that wraps, beside its RG-LRU layers
CACHES = {
    "olmo-full": ("olmo-1b", {}, 48),
    "olmo-window": ("olmo-1b", {"window_override": 8}, 48),
    "olmo-ring": ("olmo-1b", {"window_override": 8, "ring_cache": True}, 48),
    "rg-short": ("recurrentgemma-9b", {}, 48),
    "rg-ring": ("recurrentgemma-9b", {}, 128),
}


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per-row"])
@pytest.mark.parametrize("case", list(CACHES))
def test_decode_step_matches_scatter_then_attend(case, per_row):
    arch, kw, cache_len = CACHES[case]
    cfg = get_config(arch, reduced=True)
    opts = M.ModelOptions(remat=False, **kw)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    zeros = M.init_cache(cfg, ROWS, cache_len, jnp.float32, opts)
    leaves, tree = jax.tree.flatten(zeros)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    cache = jax.tree.unflatten(tree, [jax.random.normal(k, a.shape, a.dtype)
                                      for k, a in zip(keys, leaves)])
    # the first slot, a middle one, past the window, the last
    pos = (jnp.asarray([0, 11, cache_len // 2 + 3, cache_len - 1], jnp.int32)
           if per_row else jnp.asarray(cache_len // 2 + 3, jnp.int32))
    token = jnp.asarray([3, 1, 4, 1], jnp.int32)

    got, got_cache = jax.jit(lambda c: M.decode_step(
        params, token, pos, c, cfg, opts))(cache)
    want, want_cache = _decode_reference(params, token, pos, cache, cfg, opts)

    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert jax.tree.structure(got_cache) == jax.tree.structure(want_cache)
    for g, w in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-5, rtol=1e-5)
