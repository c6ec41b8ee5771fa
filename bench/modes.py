"""Runs that set a cell's numbers, not benchmark runs: the knee sweep that
fixes a cell's load, and the calibration of its comparison's limit."""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from bench import check, driver, harness, traffic


def _pct(x, q):
    return float(np.percentile(x, q)) if len(x) else float("nan")


def sweep(cell, args, peaks, out) -> None:
    """One process, one engine; per camera count, the mix held at its peak
    rate for ``--sweep-seconds``, then a full drain. A step meets the knee
    test where 95% of its frames meet their deadline and the backlog at the
    close is no more than what one deadline's worth of arrivals leaves."""
    dev = harness.device_info(args.rehearse, cell.workload["chips"], peaks)
    if not args.rehearse:
        harness.use_compile_cache()
    sizes = cell.sizes(args.rehearse)
    _, eng = harness.build(cell, args.seed, args.rehearse)
    harness.warm(eng, cell.mix)
    mix = traffic.steady(cell.mix)
    fps = traffic.peak_fps(mix)
    for k, cams in enumerate(int(c) for c in args.sweep.split(",")):
        frames = traffic.frames(mix, cams, args.sweep_seconds, args.seed + k,
                                sizes["token_vocab"])
        eng.reset_stats()
        recs, t0 = driver.drive(eng, frames, args.sweep_seconds)
        lat = np.array([r.latency_s for r in recs if r.finished])
        met = sum(r.finished and r.latency_s <= r.frame.deadline_s
                  for r in recs)
        close = t0 + args.sweep_seconds
        backlog = sum(not r.finished or r.request.finish_t > close
                      for r in recs)
        row = {"cameras": cams, "offered_frames_per_s": cams * fps,
               "frames": len(recs),
               "met_share": met / max(1, len(recs)),
               "goodput_frames_per_s": met / args.sweep_seconds,
               "p50_ms": 1e3 * _pct(lat, 50), "p95_ms": 1e3 * _pct(lat, 95),
               "backlog_at_close": backlog,
               "slot_occupancy": eng.report()["slot_occupancy"],
               "device": dev["kind"]}
        print(json.dumps(row), file=out, flush=True)


def calibrate_seed(cell, seed: int, seconds: float, rehearse: bool) -> dict:
    """The program at the cell's load for ``seconds``, then the widest gap
    of its served tokens and of the float8 control's tokens over the same
    sample."""
    sizes = cell.sizes(rehearse)
    params, eng = harness.build(cell, seed, rehearse)
    frames = traffic.frames(cell.mix, cell.cell_load(rehearse)["cameras"],
                            seconds, seed, sizes["token_vocab"])
    harness.warm(eng, cell.mix)
    recs, _ = driver.drive(eng, frames, seconds)
    eng.cache = None
    del eng
    gc.collect()
    picked = check.sample(recs, seed)
    g = check.gaps(params, cell.model_mod, sizes["model"], picked,
                   cell.mix["prompt_tokens"], cell.mix["output_tokens"][1],
                   control=True)
    return {"seed": seed, "requests": len(picked), "tokens": g["tokens"],
            "unfinished": sum(not r.finished for r in recs),
            "program_widest_gap": float(g["served"].max()),
            "control_widest_gap": float(g["control"].max()),
            "program_nonzero": int((g["served"] > 0).sum()),
            "control_nonzero": int((g["control"] > 0).sum())}


def calibrate(cell, args, peaks, out) -> None:
    """``calibrate_seed`` for seeds ``seed .. seed + n - 1``."""
    dev = harness.device_info(args.rehearse, cell.workload["chips"], peaks)
    if not args.rehearse:
        harness.use_compile_cache()
    rows = []
    for seed in range(args.seed, args.seed + args.calibrate):
        t = time.monotonic()
        row = calibrate_seed(cell, seed, args.calibrate_seconds, args.rehearse)
        row.update(seconds=time.monotonic() - t, device=dev["kind"])
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
        gc.collect()
    print(json.dumps({
        "program_max": max(r["program_widest_gap"] for r in rows),
        "control_min": min(r["control_widest_gap"] for r in rows)}),
        file=out, flush=True)
