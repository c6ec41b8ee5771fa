"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Layout: one grid row per (batch*head); the chunk axis is the sequential grid
dimension; the (state_dim x head_dim) SSM state lives in VMEM scratch and is
carried across chunks. Within a chunk everything is dense 2-D work: C@B^T
intra-chunk scores, score@x, and the rank-L state update on the MXU, and
the in-chunk prefix sum of dt*A as a reduction under the (L, L) causal mask
(Mosaic has no cumsum; the GPU version's warp-level segsum becomes this
VMEM-resident form here).

``dt`` enters lane-major, one (1, L) row per chunk; column copies are taken
with a masked reduction against the identity, which is exact. The per-head
decay ``A`` is a scalar read from SMEM, which holds all h of them.

Wrapper expectations: B/C already broadcast per head (groups expanded by the
caller); chunk divides S.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, *, L: int,
            h: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, P)
    dt_row = dt_ref[0].astype(jnp.float32)    # (1, L)
    A = a_ref[pl.program_id(0) % h]           # this head's decay (SMEM)
    Bm = b_ref[0].astype(jnp.float32)         # (L, N)
    Cm = c_ref[0].astype(jnp.float32)         # (L, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    sj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = li >= sj
    eye = li == sj
    dA_row = dt_row * A                                             # (1, L)
    cs_col = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1,
                     keepdims=True)                 # inclusive cumsum (L, 1)
    cs_row = jnp.sum(jnp.where(eye, cs_col, 0.0), axis=0,
                     keepdims=True)                                 # (1, L)
    dt_col = jnp.sum(jnp.where(eye, dt_row, 0.0), axis=1,
                     keepdims=True)                                 # (L, 1)
    cs_last = cs_col[L - 1:, :]                                     # (1, 1)

    # intra-chunk (attention-like, causal)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    scores = jnp.where(causal, cb * jnp.exp(cs_col - cs_row) * dt_row, 0.0)
    y = jax.lax.dot(scores, x, preferred_element_type=jnp.float32)  # (L, P)

    # inter-chunk contribution from the carried state (N, P)
    state = state_ref[...]
    y = y + jax.lax.dot(Cm * jnp.exp(cs_col), state,
                        preferred_element_type=jnp.float32)

    # state update: decay to end of chunk + new outer products
    w = dt_col * jnp.exp(cs_last - cs_col)                          # (L, 1)
    state_ref[...] = state * jnp.exp(cs_last) + jax.lax.dot_general(
        Bm * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                         # (N, P)

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, A, Bm, Cm, chunk: int, *, interpret: bool = False):
    """x: (b,s,h,p); dt: (b,s,h); A: (h,); Bm,Cm: (b,s,g,n). -> (b,s,h,p)."""
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    L = chunk
    assert s % L == 0, (s, L)
    nc = s // L

    xf = jnp.moveaxis(x, 2, 1).reshape(b * h, s, p)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(b * h, 1, s)
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    Bf = jnp.moveaxis(Bh, 2, 1).reshape(b * h, s, n)
    Cf = jnp.moveaxis(Ch, 2, 1).reshape(b * h, s, n)

    out = pl.pallas_call(
        functools.partial(_kernel, L=L, h=h),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, L, p), lambda r, j: (r, j, 0)),
            pl.BlockSpec((1, 1, L), lambda r, j: (r, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),      # all of A: (h,)
            pl.BlockSpec((1, L, n), lambda r, j: (r, j, 0)),
            pl.BlockSpec((1, L, n), lambda r, j: (r, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, p), lambda r, j: (r, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(xf, dtf, A.astype(jnp.float32), Bf, Cf)
    return jnp.moveaxis(out.reshape(b, h, s, p), 1, 2)
