"""RG-LRU linear-recurrence Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, elementwise over the channel (lane) dimension.
Grid: (batch, channel-blocks, seq-blocks) with the seq axis sequential; the
hidden state is a (1, bw) VMEM scratch carried across seq blocks. Within a
block the recurrence runs as an in-VMEM time loop (VPU work). A production
kernel would use a log-depth blocked scan; the sequential-in-block form keeps
the same HBM traffic (each element read once) and is the validation target.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(a_ref, b_ref, y_ref, h_ref, *, bs: int):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def body(t, h):
        a_t = a_ref[0, t, :]
        b_t = b_ref[0, t, :]
        h = a_t * h + b_t
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, body, h_ref[0, :])
    h_ref[0, :] = h


def rglru_scan(a, b, *, block_seq: int = 128, block_w: int = 512,
               interpret: bool = False):
    """a, b: (B, S, W) float32. Returns h: (B, S, W).

    Any S: a length above the sequence block that is not a multiple of it is
    right-padded to whole blocks (padding never reaches earlier steps) and
    the padded steps are dropped."""
    B, S, W = a.shape
    bs = min(block_seq, S)
    bw = min(block_w, W)
    assert W % bw == 0, (W, bw)
    Sp = -(-S // bs) * bs
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        a, b = jnp.pad(a, pad), jnp.pad(b, pad)

    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=(B, W // bw, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bi, wj, sj: (bi, sj, wj)),
            pl.BlockSpec((1, bs, bw), lambda bi, wj, sj: (bi, sj, wj)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bi, wj, sj: (bi, sj, wj)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="rglru_scan",
    )(a, b)
    return out[:, :S]
