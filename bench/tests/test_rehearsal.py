"""Every cell through the harness's CPU rehearsal, at its configuration's
reduced size: the result line has the contract's keys and is correct. A
cell defined only by new data files runs too, and the real command refuses
to run without a TPU, or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**32 + 12345          # larger than 32 bits hold


def bench(*args, cwd=ROOT, env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    out = last_line(bench("--workload", cell, "--seed", str(SEED),
                          "--seconds", "2", "--trace", str(trace),
                          "--rehearse"))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in SPEC[kind]
             if cell in m.get("workloads", [cell])}
    # a CPU run never reports a device metric under its own name
    assert all(k.startswith("cpu_rehearsal.") for k in out["metrics"])
    got = {k[len("cpu_rehearsal."):] for k in out["metrics"]}
    host = {m["name"] for m in SPEC[kind] if m["source"] != "device_trace"}
    assert got <= names and names & host <= got


def copy_bench(to: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", to)
    shutil.copytree(ROOT / "bench", to / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_new_cell_from_data_files_only(tmp_path):
    """A mix, a cell and a metric added as files, with their entries, in a
    copy of the benchmark that runs the program from this checkout."""
    copy_bench(tmp_path)
    spec = json.loads(json.dumps(SPEC))
    (tmp_path / "bench" / "traffic" / "short-burst.json").write_text(json.dumps(
        {"prompt_tokens": 64, "output_tokens": [1, 3], "deadline_s": 1.0,
         "phases": [{"seconds": 1.0, "fps": 4.0}, {"seconds": 1.0, "fps": 1.0}]}))
    (tmp_path / "bench" / "cells" / "olmo-1b.short-burst.json").write_text(
        json.dumps({"cameras": 2, "max_slots": 4, "limits": {"widest_gap": 1.0},
                    "rehearsal": {"cameras": 2, "max_slots": 4,
                                  "limits": {"widest_gap": 1.0}}}))
    (tmp_path / "bench" / "metrics" / "driver.frames.py").write_text(
        "def read(run):\n    return len(run.records)\n")
    spec["workloads"].append({"name": "olmo-1b.short-burst", "config": "olmo-1b",
                              "traffic": "short-burst", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "driver.frames", "unit": "frames",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["olmo-1b.short-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = last_line(bench("--workload", "olmo-1b.short-burst", "--seed", "3",
                          "--seconds", "2", "--trace", "0", "--rehearse",
                          cwd=tmp_path, env={"PYTHONPATH": str(ROOT / "src")}))
    assert out["correct"] is True
    assert out["metrics"]["cpu_rehearsal.driver.frames"]["value"] == \
        out["attempted"] == 10


def test_refuses_without_tpu():
    proc = bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0")
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    copy_bench(tmp_path)
    proc = bench("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0", cwd=tmp_path, env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
