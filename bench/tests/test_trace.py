"""The trace reduction, on a hand-built trace and on half-second traces of
``olmo-1b.readout-steady`` recorded on a TPU v5e (``data/``)."""
import gzip
import hashlib
import json
from pathlib import Path

import pytest

from bench import harness
from bench import trace as tr
from bench.trace import Event

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data" / "olmo_readout_0.5s.xplane.pb.gz"
KERNEL_OP = ('%flash_attention.6 = bf16[16,384,128]{2,1,0} custom-call('
             'bf16[16,384,128]{2,1,0} %a), custom_call_target="tpu_custom_call"')
OTHER_KERNEL_OP = ('%rms_norm.2 = bf16[384,128]{1,0} custom-call(bf16[384,128]'
                   '{1,0} %h), custom_call_target="tpu_custom_call"')
# sha256 of what the reduction read from each recorded trace when it kept
# only the kernels it was given a list of (flash_attention, ssd_scan):
# json.dumps of busy_s, window_s, idle_gaps, device_ops and each call's
# (index, device_s), as ``_reduced_digest`` makes it
REDUCED = {
    "olmo_readout_0.5s.xplane.pb.gz":
        "85fa1c277dbf4aceaf2e573f8e961c6d41336f75b4d2eacf9fdc250513312970",
    "olmo_readout_spans_0.5s.xplane.pb.gz":
        "ac6147a7e8e62cf59347d6370c1a451a6d398fa1e6da5493bbb9ee107dbe00c6",
}


def _synthetic():
    host = [Event("bench.window", 0.0, 10.0, {}),
            Event("engine.step", 1.0, 4.0, {}),
            Event("program.prefill", 1.2, 3.0, {"call": 0}),
            Event("engine.step", 5.0, 8.0, {}),
            Event("program.decode", 5.5, 7.5, {"call": 1}),
            Event("driver.idle", 8.5, 9.9, {})]
    mods = [Event("jit__unknown(111)", 1.3, 2.9, {}),
            Event("jit__argmax(5)", 3.2, 3.3, {}),
            Event("jit__unknown(222)", 5.6, 7.0, {})]
    ops = [Event("%fusion.1 = bf16[8] fusion(bf16[8] %x)", 1.3, 1.8, {}),
           Event(OTHER_KERNEL_OP, 1.8, 2.0, {}),
           Event(KERNEL_OP, 2.0, 2.5, {}),
           Event(KERNEL_OP, 2.5, 2.9, {}),
           Event("%argmax.1 = s32[] reduce(f32[8] %l)", 3.2, 3.3, {}),
           Event("%while.2 = (s32[]) while((s32[]) %t)", 5.6, 7.0, {}),
           Event("%fusion.9 = bf16[8] fusion(bf16[8] %y)", 5.6, 7.0, {})]
    return {"/host:CPU": {"python3": host},
            "/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def test_synthetic_trace_reduces_exactly():
    r = tr.reduce(_synthetic())
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(1.6 + 0.1 + 1.4)
    prefill, decode = r["calls"][0], r["calls"][1]
    assert (prefill.kind, prefill.launches) == ("prefill", 1)
    assert prefill.device_s == pytest.approx(1.6)
    # every kernel by its name, with no list of names
    assert prefill.kernel_n == {"flash_attention": 2, "rms_norm": 1}
    assert prefill.kernel_s["flash_attention"] == pytest.approx(0.9)
    assert prefill.kernel_s["rms_norm"] == pytest.approx(0.2)
    assert (decode.kind, decode.launches, decode.kernel_n) == ("decode", 1, {})
    # gaps in falling length, labelled by the innermost open span
    assert [g[0] for g in r["idle_gaps"]] == [
        "driver.idle", "outside spans", "outside spans", "engine.step"]
    assert [round(g[1], 6) for g in r["idle_gaps"]] == [3.0, 2.3, 1.3, 0.3]
    # container operations are left out of the top list
    names = [k for k, _ in r["device_ops"]]
    assert not any("while" in n for n in names)
    assert names[0] == "decode: %fusion.9 = bf16[8] fusion"
    assert "prefill: %flash_attention.6 = bf16[16,384,128] custom-call" in names


def test_no_window_or_no_device_reads_nothing():
    planes = _synthetic()
    assert tr.reduce({"/host:CPU": planes["/host:CPU"]}) is None
    planes["/host:CPU"]["python3"] = planes["/host:CPU"]["python3"][1:]
    assert tr.reduce(planes) is None


@pytest.fixture(scope="module")
def chip():
    return tr.reduce(tr.load(gzip.decompress(DATA.read_bytes())))


def test_chip_trace_programs_and_kernels(chip):
    calls = chip["calls"]
    kinds = {c.kind for c in calls.values()}
    assert kinds == {"prefill", "decode"}
    for c in calls.values():
        assert c.launches == 1
        if c.kind == "prefill":
            # one flash_attention launch in each of OLMo-1B's 16 layers
            assert c.kernel_n == {"flash_attention": 16}
            assert 0 < c.kernel_s["flash_attention"] < c.device_s
        else:
            assert c.kernel_n == {}
    assert 0 < chip["busy_s"] <= chip["window_s"]
    gaps = [g[1] for g in chip["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= chip["window_s"] - chip["busy_s"] + 1e-9
    assert len(chip["device_ops"]) == tr.TOP


def _reduced_digest(r) -> str:
    core = {"busy_s": r["busy_s"], "window_s": r["window_s"],
            "idle_gaps": r["idle_gaps"], "device_ops": r["device_ops"],
            "device_s": [[i, c.device_s] for i, c in sorted(r["calls"].items())]}
    return hashlib.sha256(json.dumps(core).encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(REDUCED))
def test_chip_traces_reduce_as_with_a_list_of_kernels(name):
    data = Path(__file__).parent / "data" / name
    r = tr.reduce(tr.load(gzip.decompress(data.read_bytes())))
    assert _reduced_digest(r) == REDUCED[name]
    prefills = [c for c in r["calls"].values() if c.kind == "prefill"]
    assert prefills
    for c in r["calls"].values():
        assert c.kernel_n == ({"flash_attention": 16} if c.kind == "prefill"
                              else {})
        assert set(c.kernel_s) == set(c.kernel_n)


def _run_data(chip, model, calls_log):
    cell = harness.load_cell("olmo-1b.readout-steady")
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    return harness.RunData(cell, model, 0.5, 0.0, [], {}, peaks["TPU v5 lite"],
                           0.0, calls_log, chip)


def test_chip_trace_readers_stay_under_100_percent(chip):
    model = json.loads((ROOT / "bench" / "configs" / "olmo-1b.json").read_text())["model"]
    n = max(chip["calls"]) + 1
    log = [{"kind": "prefill", "S": 272} if c.kind == "prefill"
           else {"kind": "decode", "positions": [300] * 4}
           for c in (chip["calls"].get(i) for i in range(n)) if c is not None]
    log = {i: e for i, e in zip(sorted(chip["calls"]), log)}
    run = _run_data(chip, model, [log.get(i, {}) for i in range(n)])
    roof = harness.reader("flash_attention_roofline")(run)
    mfu = harness.reader("step.mfu")(run)
    idle = harness.reader("device.idle_share")(run)
    pre = harness.reader("step.prefill_ms")(run)
    assert 0 < roof < 100 and 0 < mfu < 100 and 0 < idle < 100
    # per layer: 272 rows, 16 heads of 128: max(0.304 GFLOP / 197 TFLOP/s,
    # 4.46 MB / 819 GB/s) = 5.45 us against about 75 us measured
    assert 5 < roof < 10
    assert 10 < pre < 20
    assert harness.reader("ssd_scan_roofline")(run) is None
