"""Flash attention Pallas TPU kernel (GQA + causal + sliding window).

TPU adaptation: online-softmax with the KV dimension as the innermost
("arbitrary"/sequential) grid axis; m/l/acc VMEM scratch persists across KV
blocks of one query block and the output block is written on the last KV
step. Block shapes default to 128x128 (MXU-aligned); head_dim is the lane
dimension. GQA is expressed in the K/V index_map (query row -> kv row), so
no KV replication is materialized.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            nk: int, q_off: int, kv_len: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                      # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                      # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                      # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    i = pl.program_id(1)
    q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_off
    k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if kv_len < nk * bk:                # right-padded keys
        mask &= k_idx < kv_len
    if causal:
        mask &= k_idx <= q_idx
    if window > 0:
        mask &= k_idx > q_idx - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B,S,H,hd); k,v: (B,T,K,hd). Returns (B,S,H,hd).

    Any S and T: a length above its block size that is not a multiple of it
    is right-padded to whole blocks. Padded keys are masked out and padded
    query rows are dropped, so the result is exact."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(bq, S)
    bk = min(bk, T)
    nq, nk = -(-S // bq), -(-T // bk)
    q_off = T - S                       # queries are the last S of T positions

    def rows(x, n):                     # (B,L,heads,hd) -> (B*heads, n, hd)
        x = jnp.moveaxis(x, 2, 1)
        x = jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))
        return x.reshape(-1, n, hd)

    qf, kf, vf = rows(q, nq * bq), rows(k, nk * bk), rows(v, nk * bk)

    def kv_row(bh, i, j):
        return (bh // H) * K + (bh % H) // G

    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk, q_off=q_off,
                          kv_len=T),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, i, j: (kv_row(bh, i, j), j, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, i, j: (kv_row(bh, i, j), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return jnp.moveaxis(out[:, :S].reshape(B, H, S, hd), 1, 2)
