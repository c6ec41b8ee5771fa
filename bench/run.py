"""Runs one benchmark cell once and prints its result as the last line of
standard output.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks for.
The command exits non-zero, printing no result, where JAX finds no TPU,
fewer chips than the cell needs, or a device not in ``bench/peaks.json``.

Modes that are not part of a benchmark run:

- ``--rehearse``: the cell at its configuration's ``rehearsal`` (reduced)
  size on whatever backend JAX has, for the CPU. Its metrics are named
  ``cpu_rehearsal.<metric>``: a CPU run gives no device metric.
- ``--sweep <cameras,...> [--sweep-seconds s]``: the cell's mix held at its
  peak rate, one step per camera count in one process, one JSON row per
  step; finds the knee a cell's fixed load is set from.
- ``--calibrate <n> [--calibrate-seconds s]``: seeds ``seed .. seed+n-1``
  in one process, each a short window at the cell's load, and per seed the
  comparison's widest gap for the program and for the float8 control.
- ``--save-trace <file>``: with ``--trace 1``, keeps the profiler's
  ``.xplane.pb`` of the window for a look at what the reduction left out.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-seconds", type=float, default=10.0)
    ap.add_argument("--calibrate", type=int, default=0)
    ap.add_argument("--calibrate-seconds", type=float, default=6.0)
    ap.add_argument("--save-trace", default=None)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import harness, modes
    try:
        cell = harness.load_cell(args.workload)
        peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
        with harness.quiet_stdout() as out:
            if args.sweep:
                modes.sweep(cell, args, peaks, out)
                return 0
            if args.calibrate:
                modes.calibrate(cell, args, peaks, out)
                return 0
            result = harness.run(cell, args.seed, args.seconds,
                                 trace=bool(args.trace),
                                 rehearse=args.rehearse, t_start=T_START,
                                 peaks=peaks, save_trace=args.save_trace)
    except harness.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
