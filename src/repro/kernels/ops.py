"""Jitted wrappers around the Pallas kernels with platform dispatch.

On TPU the kernels run compiled. On the CPU they run in interpret mode
(Python execution of the kernel body), so CPU tests validate the exact
kernel code that would run on hardware. Any other backend is refused rather
than silently interpreted.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"Pallas TPU kernels run compiled on 'tpu' or interpreted on 'cpu', "
        f"not on {backend!r}")


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, chunk: int):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk, interpret=_interpret())


@jax.jit
def rglru_scan(a, b):
    return _rg.rglru_scan(a, b, interpret=_interpret())
