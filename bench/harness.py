"""One run of one benchmark cell, driven by data.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Everything that
belongs to it is found by name under ``bench/``:

- ``configs/<config>.json``   the model as it is run (``model``: the
  program's config is built from these sizes), the engine's
  ``cache_len``, the dtype, and a ``rehearsal`` block of the same keys at
  the program's reduced size for the CPU;
- ``traffic/<mix>.json``      the traffic mix (``bench.traffic``);
- ``cells/<cell>.json``       the cell's fixed load (``cameras``), the
  engine's slot pool sized to it (``max_slots``) and the limits of its
  comparison, and the same under ``rehearsal``;
- ``models/<config>.py``      the model module: the weights in the
  program's layout and the reference's equations (``bench.models``);
- ``metrics/<metric>.py``     one reader per metric: ``read(run)`` returns
  a number, or ``None`` where it finds nothing to read;
- ``costs/``, ``peaks.json``  operations and bytes per call, chip peaks.

The trace's reduction keeps the device time of every Pallas kernel by its
name, so a new kernel needs only its cost file and its reader.

The run: weights from the seed on the device (``bench.weights``), the
program's ``ContinuousBatchingEngine`` at the cell's slots and the
configuration's cache length, a warm-up of exactly the cell's shapes, the open-loop window
(``bench.driver``), the drain, then the comparison with the plain reference
(``bench.check``) once the program's state is freed. ``--trace 1`` records
a profiler trace of the window and reads the per-layer metrics from it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models.config import get_config
from repro.serving import ContinuousBatchingEngine, Request

from bench import check, driver, traffic, weights
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(RuntimeError):
    """The run cannot be made here (no chip, unknown device, bad spec)."""


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict           # the configuration file
    mix: dict              # the traffic file
    load: dict             # the cell file
    metrics: dict          # metric name -> BENCHMARK.json entry
    model_mod: ModuleType  # bench/models/<config>.py

    def sizes(self, rehearse: bool) -> dict:
        return self.config["rehearsal"] if rehearse else self.config

    def cell_load(self, rehearse: bool) -> dict:
        return self.load["rehearsal"] if rehearse else self.load

    def metric_names(self, per_layer: bool) -> list:
        return [m["name"] for m in self.metrics.values()
                if (m["_kind"] == "per_layer") == per_layer
                and self.name in m.get("workloads", [self.name])]


def load_cell(name: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            metrics[m["name"]] = {**m, "_kind": kind}
    read = lambda p: json.loads((ROOT / p).read_text())
    return Cell(name, w, read(conf["file"]),
                read(f"bench/traffic/{w['traffic']}.json"),
                read(f"bench/cells/{name}.json"), metrics,
                model_module(w["config"]))


def _load(path: Path, prefix: str) -> ModuleType:
    """The Python file ``path`` as a module, whatever dots and hyphens its
    name holds."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    return _load(ROOT / "bench" / "metrics" / f"{name}.py", "bench_metric_").read


@functools.lru_cache(maxsize=None)
def model_module(config: str) -> ModuleType:
    """``bench/models/<config>.py``, loaded once per process: the jitted
    weights and reference take the module as a static argument, so one
    object means one compile."""
    path = ROOT / "bench" / "models" / f"{config}.py"
    if not path.is_file():
        raise Refused(f"no model module {path.relative_to(ROOT)} for "
                      f"configuration {config!r}")
    return _load(path, "bench_model_")


def program_config(arch: str, model: dict, rehearse: bool):
    """The program's config for ``arch`` with every size that the
    configuration file's ``model`` states put in, so the file, and not the
    program's registry, says what runs."""
    sizes = {k: tuple(map(tuple, v)) if k == "block_pattern" else v
             for k, v in model.items()}
    return dataclasses.replace(get_config(arch, reduced=rehearse), **sizes)


def device_info(rehearse: bool, chips: int, peaks: dict) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        return info
    if info["platform"] != "tpu":
        raise Refused(f"JAX found no TPU (platform {info['platform']!r}); "
                      f"the benchmark measures on the chip only")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{info['count']}")
    if info["kind"] not in peaks:
        raise Refused(f"device {info['kind']!r} is not in bench/peaks.json")
    info["count"] = chips
    return info


def use_compile_cache() -> None:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, for
    every program however quickly it compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CompileCounter:
    """Counts lowerings and backend compiles inside its ``with`` block."""

    def __enter__(self):
        self.counts = {e: 0 for e in COMPILE_EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, duration, **kw):
        if event in self.counts:
            self.counts[event] += 1


class Tap:
    """Wraps the engine's two programs and its admission for the traced
    run: each program call runs inside a host span named after its kind and
    index and is blocked on there, so the span covers its device work; the
    call's active slots and positions are logged for the cost model, and
    each request's admission time is kept."""

    def __init__(self, eng):
        self.calls: list = []
        self.admit_t: dict = {}
        self._wrap(eng, "_prefill_slot", "prefill")
        self._wrap(eng, "_decode", "decode")
        admit = eng._admit

        def _admit(req, slot):
            self.admit_t[req.request_id] = time.monotonic()
            return admit(req, slot)
        eng._admit = _admit

    def _wrap(self, eng, attr, kind):
        program = getattr(eng, attr)

        def call(params, cache, batch, *slot):
            i = len(self.calls)
            if kind == "prefill":
                entry = {"kind": kind, "S": int(batch["tokens"].shape[1])}
            else:
                pos = np.asarray(batch["pos"])
                entry = {"kind": kind, "positions":
                         [int(pos[s]) for s in eng.active_slots()]}
            self.calls.append(entry)
            with jax.profiler.TraceAnnotation(f"program.{kind}", call=i):
                out = program(params, cache, batch, *slot)
                jax.block_until_ready(out)
            return out
        setattr(eng, attr, call)


def warm(eng, mix: dict) -> None:
    """Exactly the cell's shapes: admissions at the mix's prompt length and
    decode steps over all slots (with the host argmax of each)."""
    toks = np.zeros(mix["prompt_tokens"], np.int32)
    for i in range(2):
        eng.submit(Request(f"warm-{i}", toks.copy(), max_new_tokens=3))
    eng.drain()
    eng.submit(Request("warm-2", toks.copy(), max_new_tokens=2))
    eng.drain()
    eng.reset_stats()


@dataclasses.dataclass
class RunData:
    """What a metric reader reads."""
    cell: Cell
    model: dict
    seconds: float
    setup_s: float
    records: list
    report: dict
    peak: Optional[dict]
    end_t: float = 0.0
    calls: list = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None

    def latencies(self) -> np.ndarray:
        """Due time to last token of every frame due in the window; a frame
        the drain gave up on counts as waiting until then."""
        return np.array([r.latency_s if r.finished else self.end_t - r.due_t
                         for r in self.records])

    def traced_calls(self, kind: str) -> list:
        """(log entry, traced call) of each ``kind`` call in the window."""
        if not self.trace:
            return []
        return [(self.calls[i], c) for i, c in
                sorted(self.trace["calls"].items()) if c.kind == kind]


def build(cell: Cell, seed: int, rehearse: bool):
    sizes = cell.sizes(rehearse)
    cfg = program_config(cell.config["arch"], sizes["model"], rehearse)
    params = weights.make_weights(cell.model_mod, sizes["model"], seed,
                                  cell.config["dtype"])
    jax.block_until_ready(params)
    dtype = weights.DTYPES[cell.config["dtype"]]
    weights.check_layout(params, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype)))
    eng = ContinuousBatchingEngine(
        cfg, params, max_slots=cell.cell_load(rehearse)["max_slots"],
        cache_len=sizes["cache_len"])
    return params, eng


def run(cell: Cell, seed: int, seconds: float, *, trace: bool,
        rehearse: bool, t_start: float, peaks: dict,
        fault: Optional[Callable] = None, save_trace: Optional[str] = None,
        out=sys.stderr) -> dict:
    sizes = cell.sizes(rehearse)
    load = cell.cell_load(rehearse)
    dev = device_info(rehearse, cell.workload["chips"], peaks)
    if not rehearse:
        use_compile_cache()
    params, eng = build(cell, seed, rehearse)
    if fault is not None:
        fault(eng)
    frames = traffic.frames(cell.mix, load["cameras"], seconds, seed,
                            sizes["token_vocab"])
    warm(eng, cell.mix)

    tap, span, tdir = None, driver._nospan, None
    if trace:
        # no Python tracer (its events would swamp the window); the first
        # executions under the profiler run here, in set-up
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=opts)
        warm(eng, cell.mix)
        tap = Tap(eng)
        span = jax.profiler.TraceAnnotation
    with CompileCounter() as counter:
        t0 = time.monotonic()
        setup_s = t0 - t_start
        records, _ = driver.drive(eng, frames, seconds, span=span, t0=t0)
        end_t = time.monotonic()
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        for r in records:
            r.admit_t = tap.admit_t.get(r.request.request_id, float("nan"))
    print(f"compiles in the window and drain: lowerings "
          f"{counter.counts[COMPILE_EVENTS[0]]}, backend compiles "
          f"{counter.counts[COMPILE_EVENTS[1]]}", file=out)
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    report = eng.report()
    eng.cache = None
    del eng
    gc.collect()
    if trace:
        path = tr.find_xplane(tdir)
        reduced = tr.reduce(tr.load(path)) if path else None
        if save_trace and path:
            shutil.copy(path, save_trace)
        shutil.rmtree(tdir, ignore_errors=True)
        if reduced is not None:
            dev["busy_s"] = reduced["busy_s"]
            dev["window_s"] = reduced["window_s"]

    # the comparison with the reference
    t_ref = time.monotonic()
    picked = check.sample(records, seed)
    g = check.gaps(params, cell.model_mod, sizes["model"], picked,
                   cell.mix["prompt_tokens"], cell.mix["output_tokens"][1])
    # nothing to compare is as bad as the worst gap: finite, so the line
    # stays plain JSON
    widest = float(g["served"].max()) if g["tokens"] else 1e9
    unfinished = sum(not r.finished for r in records)
    wrong_len = sum(r.finished and len(r.request.output) != r.frame.new_tokens
                    for r in records)
    limits = load["limits"]
    checks = {
        "widest_gap": {"value": widest, "limit": limits["widest_gap"]},
        "unfinished": {"value": unfinished, "limit": 0},
        "wrong_length": {"value": wrong_len, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    print(f"reference: {len(picked)} requests, {g['tokens']} served tokens "
          f"compared in {time.monotonic() - t_ref:.2f} s", file=out)

    data = RunData(cell, sizes["model"], seconds, setup_s, records, report,
                   None if rehearse else peaks[dev["kind"]], end_t,
                   tap.calls if tap else [], reduced)
    metrics = {}
    prefix = "cpu_rehearsal." if rehearse else ""
    for name in cell.metric_names(per_layer=trace):
        v = reader(name)(data)
        if v is not None:
            metrics[prefix + name] = {"value": float(v),
                                      "unit": cell.metrics[name]["unit"]}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": unfinished, "metrics": metrics, "device": dev}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def print_result(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()


@contextlib.contextmanager
def quiet_stdout():
    """Routes stdout to stderr, so the result is standard output's last
    line whatever the libraries print."""
    saved = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield saved
    finally:
        sys.stdout = saved
