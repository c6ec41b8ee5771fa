"""Every cell through the harness's CPU rehearsal, at its configuration's
reduced size: the result line has the contract's keys and is correct. A
cell defined only by new data files runs too, and so does a configuration
with a layer kind of its own, defined only by new files; the real command
refuses to run without a TPU, or without the program."""
import hashlib
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


WINDOW_MODULE = '''"""OLMo-1B's blocks with sliding-window attention (``attn_window``):
each query attends to the ``window`` latest positions, itself included."""
import functools
import math

import jax
import jax.numpy as jnp

from bench.models import _decoder as d
from bench.reference import HI, mm


def attn_window(p, h, m, quant):
    B, S, _ = h.shape
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q = d.rope(mm(h, p["wq"], quant).reshape(B, S, H, hd), m["rope_theta"])
    k = d.rope(mm(h, p["wk"], quant).reshape(B, S, K, hd), m["rope_theta"])
    v = mm(h, p["wv"], quant).reshape(B, S, K, hd)
    k, v = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI) / math.sqrt(hd)
    t = jnp.arange(S)
    mask = t[None, :] <= t[:, None]
    mask &= t[None, :] > t[:, None] - m["window"]
    w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    o = jnp.einsum("bhst,bthd->bshd", w, v, precision=HI).reshape(B, S, H * hd)
    return mm(o, p["wo"], quant)


make = functools.partial(
    d.make, mixers={**d.MIXER_WEIGHTS, "attn_window": d.attn_weights})
layer = functools.partial(d.layer, mixers={**d.MIXERS, "attn_window": attn_window})
embed, head = d.embed, d.head
'''
WINDOW_LINE = '    mask &= t[None, :] > t[:, None] - m["window"]\n'


def _digests(root: Path) -> dict:
    return {str(f.relative_to(root)): hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(root.rglob("*"))
            if f.is_file() and "__pycache__" not in f.parts}


def test_new_configuration_from_new_files_only(tmp_path):
    """A configuration whose layers are ``attn_window`` (the program's ring
    cache), which the shared decoder lacks: its file, its model module, a
    mix whose prompts outrun the window, and a cell, all new files with
    their entries. Correct with the module's window; not correct with the
    window taken out of the module, so the module the configuration names
    is the one the comparison used. No file taken from the tree changes."""
    copy_bench(tmp_path)
    taken = _digests(tmp_path)
    del taken["BENCHMARK.json"]          # entries are added to it, below
    conf = json.loads((ROOT / "bench" / "configs" / "olmo-1b.json").read_text())
    for sizes in (conf, conf["rehearsal"]):
        sizes["model"]["block_pattern"] = [["attn_window", "mlp"]]
        sizes["model"]["window"] = 16
    bench_dir = tmp_path / "bench"
    (bench_dir / "configs" / "olmo-1b-window.json").write_text(json.dumps(conf))
    module = bench_dir / "models" / "olmo-1b-window.py"
    module.write_text(WINDOW_MODULE)
    (bench_dir / "traffic" / "window-readout.json").write_text(json.dumps(
        {"prompt_tokens": 48, "output_tokens": [4, 12], "deadline_s": 1.0,
         "phases": [{"seconds": 1.0, "fps": 2.0}]}))
    limits = {"cameras": 3, "max_slots": 4, "limits": {"widest_gap": 0.035}}
    (bench_dir / "cells" / "olmo-1b-window.window-readout.json").write_text(
        json.dumps({**limits, "rehearsal": limits}))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "olmo-1b-window", "source": "test",
                            "file": "bench/configs/olmo-1b-window.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "olmo-1b-window.window-readout",
                              "config": "olmo-1b-window",
                              "traffic": "window-readout", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert spec[key][:len(SPEC[key])] == SPEC[key]

    def rehearse():
        return last_line(bench("--workload", "olmo-1b-window.window-readout",
                               "--seed", str(SEED), "--seconds", "2",
                               "--trace", "0", "--rehearse", cwd=tmp_path,
                               env={"PYTHONPATH": str(ROOT / "src")}))

    ok = rehearse()
    assert ok["correct"] is True, ok["checks"]
    assert ok["attempted"] > 0 and ok["checks"]["widest_gap"]["value"] < 0.035
    assert WINDOW_LINE in WINDOW_MODULE
    module.write_text(WINDOW_MODULE.replace(WINDOW_LINE, ""))
    full = rehearse()
    assert full["correct"] is False
    assert full["checks"]["widest_gap"]["value"] > 0.035
    after = _digests(tmp_path)
    assert {k: after[k] for k in taken} == taken == {
        f"bench/{k}": v for k, v in _digests(ROOT / "bench").items()}


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
