"""Serving launcher: allocation-managed multi-stream serving demo.

Serves simulated camera streams on the continuous-batching engine first (the
measurement phase — the paper's empirical profiling step), then plans the
fleet with the resource manager from the *measured* per-stream tokens/sec
and reports cost, throughput, and SLO attainment. CPU-sized by default
(reduced configs, f32 weights); ``--full`` serves the published widths in
bf16 on one chip, with the architecture's Pallas kernels on TPU:

  PYTHONPATH=src python -m repro.launch.serve --full
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.tpu_catalog import plan_tpu_fleet, streams_from_measured
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models.config import ArchConfig, get_config, list_archs
from repro.serving import (ContinuousBatchingEngine, Request, ServingEngine,
                           StreamSimulator)


def _warmup(eng, prompt_len: int, new_tokens: int) -> None:
    """Compile the prefill/decode paths outside the measurement window and
    reset the stats — otherwise one-time jit cost deflates the measured
    rates the fleet planner consumes. The static engine compiles per batch
    shape, so warm it at its full max_batch (the continuous engine always
    prefills B=1 and decodes B=max_slots, so one request covers both)."""
    n = getattr(eng, "max_batch", 1)
    toks = np.zeros(prompt_len, np.int32)
    for i in range(n):
        eng.submit(Request(f"warmup-{i}", toks.copy(),
                           max_new_tokens=new_tokens))
    eng.drain()
    eng.reset_stats()


_init_params = jax.jit(M.init_params, static_argnums=(0, 2))


def init_weights(cfg: ArchConfig, *, reduced: bool):
    """Random weights from ``PRNGKey(0)``: f32 for the reduced CPU configs,
    bf16 at full width. The init runs under jit, so full-width weights are
    produced in bf16 directly and never held in f32."""
    dtype = jnp.float32 if reduced else jnp.bfloat16
    return _init_params(cfg, jax.random.PRNGKey(0), dtype)


def serve(arch: str = "olmo-1b", *, n_streams: int = 4, fps: float = 2.0,
          seconds: int = 3, reduced: bool = True,
          engine: str = "continuous") -> dict:
    # 1) serve the streams and measure throughput
    cfg = get_config(arch, reduced=reduced)
    params = init_weights(cfg, reduced=reduced)
    if engine == "continuous":
        eng = ContinuousBatchingEngine(cfg, params, max_slots=8,
                                       cache_len=128)
    elif engine == "static":
        eng = ServingEngine(cfg, params, max_batch=8, cache_len=128)
    else:
        raise ValueError(engine)
    t0 = time.monotonic()
    _warmup(eng, prompt_len=32, new_tokens=8)
    warmup_s = time.monotonic() - t0
    sim = StreamSimulator(eng, prompt_len=32, new_tokens=8)
    done = []
    for t in range(seconds):
        sim.tick({f"cam-{i}": fps for i in range(n_streams)}, dt_s=1.0)
        done.extend(eng.drain())

    # 2) per-stream measured rates feed the packing machinery (the paper's
    # profile-then-pack loop, with closed-form per-stream requirements);
    # streams that served no frames fall back to their nominal
    # fps x tokens-per-frame target
    measured = eng.measured_rates()
    for i in range(n_streams):
        measured.setdefault(f"cam-{i}", fps * 8)

    streams = streams_from_measured(arch, measured)
    plans = {s: plan_tpu_fleet(streams, strategy=s)
             for s in ("per-stream", "uniform-big", "packed")}
    packed, per_stream = plans["packed"], plans["per-stream"]
    savings = 1.0 - packed["hourly_cost"] / per_stream["hourly_cost"]
    out = {
        "arch": arch,
        "engine": engine,
        "warmup_s": round(warmup_s, 2),
        "frames_served": len(done),
        "tokens_per_frame": sorted({len(r.output) for r in done}),
        "tokens_per_s": round(eng.throughput_tokens_per_s(), 1),
        "measured_stream_tokens_per_s": {k: round(v, 1)
                                         for k, v in sorted(measured.items())},
        "fleet_plans": plans,
        "packed_vs_per_stream_savings": round(savings, 3),
    }
    if isinstance(eng, ContinuousBatchingEngine):
        rep = eng.report()
        out["serving_report"] = {k: round(v, 4) if isinstance(v, float) else v
                                 for k, v in rep.items()}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="olmo-1b")
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--fps", type=float, default=2.0)
    ap.add_argument("--seconds", type=int, default=3)
    ap.add_argument("--engine", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="published widths in bf16 (default: reduced, f32)")
    args = ap.parse_args()
    enable_compile_cache()
    out = serve(args.arch, n_streams=args.streams, fps=args.fps,
                seconds=args.seconds, reduced=args.reduced,
                engine=args.engine)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
