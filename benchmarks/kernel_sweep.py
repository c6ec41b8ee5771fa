"""Benchmark: Pallas kernel validation matrix — max |err| vs the jnp oracle
across shapes, through the ``repro.kernels.ops`` wrappers (the kernels are
the TPU hot-spot implementations for attention / SSD / RG-LRU workloads).

``ops`` runs the kernels compiled on a TPU backend and in interpret mode on
the CPU; each row's ``derived`` names the mode. ``us_per_call`` is one warm
call (after a compiling call) on the host clock: in interpret mode it times
the Python interpreter, not a kernel."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _warm_call_us(fn, *args) -> tuple[float, jnp.ndarray]:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) * 1e6, out


def run() -> list[dict]:
    rng = np.random.default_rng(7)
    mode = "compiled" if ops.on_tpu() else "interpret"
    rows = []

    for (S, H, hd, K, win) in [(256, 4, 64, 2, 0), (256, 8, 128, 2, 64),
                               (512, 4, 64, 1, 0)]:
        q = jnp.asarray(rng.standard_normal((1, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, S, K, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, S, K, hd)), jnp.float32)
        us, out = _warm_call_us(
            lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                window=win), q, k, v)
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(
            ref.flash_attention_ref(q, k, v, causal=True, window=win)))))
        rows.append({"name": f"flash_attn_S{S}_H{H}_K{K}_w{win}",
                     "us_per_call": us, "derived": f"max_err={err:.1e} {mode}"})

    for (s, h, p, n, L) in [(256, 4, 64, 64, 64), (128, 8, 32, 128, 128)]:
        x = jnp.asarray(rng.standard_normal((1, s, h, p)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.001, 0.1, (1, s, h)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 2, (h,)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((1, s, 1, n)), jnp.float32)
        C = jnp.asarray(rng.standard_normal((1, s, 1, n)), jnp.float32)
        us, out = _warm_call_us(
            lambda *a: ops.ssd_scan(*a, chunk=L), x, dt, A, B, C)
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(
            ref.ssd_scan_ref(x, dt, A, B, C, L)))))
        rows.append({"name": f"ssd_scan_S{s}_H{h}_N{n}_chunk{L}",
                     "us_per_call": us, "derived": f"max_err={err:.1e} {mode}"})

    for (S, W) in [(256, 512), (512, 256)]:
        a = jnp.asarray(rng.uniform(0.7, 0.999, (1, S, W)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((1, S, W)), jnp.float32)
        us, out = _warm_call_us(ops.rglru_scan, a, b)
        err = float(np.max(np.abs(np.asarray(out) -
                                  np.asarray(ref.rglru_scan_ref(a, b)))))
        rows.append({"name": f"rglru_scan_S{S}_W{W}", "us_per_call": us,
                     "derived": f"max_err={err:.1e} {mode}"})
    return rows
