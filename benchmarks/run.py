"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

Prints ``name,us_per_call,derived`` CSV per the repository convention.
``--only <suite>`` runs a single suite (e.g. ``--only fleet_sim`` as a CI
smoke job) instead of the full sweep; ``--list`` shows the suite keys.
"""
from __future__ import annotations

import argparse
import os
import sys

# Allow `python benchmarks/run.py` from the repo root without PYTHONPATH
# gymnastics: the harness needs the repo root (for `benchmarks.*`) and src/
# (for `repro.*`) importable.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


# (key, title, module under benchmarks/). Modules import lazily so that
# `--only fleet_sim` (the CI smoke job) neither pays for nor breaks on the
# jax-heavy suites it does not run.
_SUITES: list[tuple[str, str, str]] = [
    ("fig3", "fig3 (CPU/GPU selection)", "fig3_cpu_gpu"),
    ("table1", "table1 (price disparity)", "table1_catalog"),
    ("fig6", "fig6 (location strategies)", "fig6_location"),
    ("speedup", "speedup (GPU vs fps)", "speedup_table"),
    ("adaptive", "adaptive (rush hour)", "adaptive_runtime"),
    ("solver", "solver scaling", "solver_scaling"),
    ("tpu_fleet", "tpu fleet (beyond-paper)", "tpu_fleet"),
    ("continuous", "continuous vs static batching (beyond-paper)",
     "continuous_vs_static"),
    ("fleet_sim", "fleet simulator (beyond-paper)", "fleet_sim"),
    ("replan_churn", "replan churn: REPAIR vs FFD full replan (beyond-paper)",
     "replan_churn"),
    ("spot_bidding", "spot bidding: mixed plans vs on-demand-only "
     "(beyond-paper)", "spot_bidding"),
    ("drift_recalibration", "drift recalibration: online vs stale profile "
     "(beyond-paper)", "drift_recalibration"),
    ("scale_sweep", "scale sweep: 100/1k/10k streams, packed vs scalar "
     "(beyond-paper)", "scale_sweep"),
    ("columnar_sweep", "columnar sweep: 1M-stream day, columnar vs object "
     "event loop (beyond-paper)", "columnar_sweep"),
    ("obs_export", "observability exporters + per-group recalibration "
     "(beyond-paper)", "obs_export"),
    ("pipeline_consolidation", "content-aware pipelines: crop consolidation "
     "vs per-camera stages (beyond-paper)", "pipeline_consolidation"),
    ("forecast_mpc", "seasonal forecast + MPC autoscaling vs reactive "
     "(beyond-paper)", "forecast_mpc"),
    ("kernels", "pallas kernels vs oracle (compiled on TPU, interpreted on "
     "CPU)",
     "kernel_sweep"),
]


def main() -> None:
    import importlib

    suites = _SUITES
    keys = [k for k, _, _ in suites]
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None, metavar="SUITE",
                    help="run a single suite instead of the full sweep "
                         f"(one of: {', '.join(keys)})")
    ap.add_argument("--list", action="store_true", help="list suite keys")
    args = ap.parse_args()
    if args.list:
        print("\n".join(keys))
        return
    if args.only is not None:
        if args.only not in keys:
            # a typo must fail loudly with the catalog, never run nothing
            ap.error(f"unknown suite {args.only!r}; known suites: "
                     f"{', '.join(keys)}")
        suites = [s for s in suites if s[0] == args.only]

    print("name,us_per_call,derived")
    mismatches = 0
    for _, title, mod in suites:
        print(f"# --- {title} ---")
        run_fn = importlib.import_module(f"benchmarks.{mod}").run
        for row in run_fn():
            ok = row.get("match_paper")
            tail = "" if ok is None else ("  [MATCHES PAPER]" if ok
                                          else "  [MISMATCH]")
            if ok is False:
                mismatches += 1
            print(f"{row['name']},{row['us_per_call']:.1f},"
                  f"\"{row['derived']}{tail}\"")

    if mismatches:
        print(f"# WARNING: {mismatches} cells mismatch the paper")
        sys.exit(1)


if __name__ == "__main__":
    main()
