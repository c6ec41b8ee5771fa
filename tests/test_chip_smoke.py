"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at the reduced
configs (the Pallas kernels in interpret mode), its gates, and its refusal to
run anywhere but on a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase_reduced(chip_smoke):
    cases = chip_smoke.kernel_cases(reduced=True)
    errs = chip_smoke.phase_kernels(cases, interpret=True)
    assert len(errs) == len(cases) == 6
    assert max(errs.values()) < 1e-5


def test_kernel_phase_fails_over_tolerance(chip_smoke):
    name, kind, inputs, kernel, oracle = chip_smoke.kernel_cases(
        reduced=True)[-1]
    off = (name, kind, inputs,
           lambda a, b, interpret: kernel(a, b, interpret=interpret) + 0.1,
           oracle)
    with pytest.raises(chip_smoke.SmokeFailure, match="over tolerance"):
        chip_smoke.phase_kernels([off], interpret=True)


def test_serving_phase_reduced(chip_smoke):
    out = chip_smoke.phase_serving(reduced=True)
    assert out["serving_report"]["requests"] == 24


def test_reference_phase_reduced_with_interpreted_kernels(chip_smoke,
                                                         monkeypatch):
    """The engine turns the kernels on for a TPU backend only; here the test
    turns them on so that the phase runs them in interpret mode."""
    from repro.models.model import ModelOptions
    from repro.serving import engine
    monkeypatch.setattr(engine, "serving_options",
                        lambda: ModelOptions(remat=False, use_kernels=True))
    out = chip_smoke.phase_reference(reduced=True)
    n = len(chip_smoke.REF_REQUESTS)
    assert len(out["ref_rel_l2"]) == len(out["cache_rel_l2"]) == n
    assert max(out["ref_rel_l2"]) < 1e-4
    assert max(out["cache_rel_l2"]) < 1e-4
    # interpret mode lowers to plain HLO
    assert out["has_kernel"] == {32: False, 200: False}


def test_reference_phase_catches_a_wrong_slot_position(chip_smoke,
                                                        monkeypatch):
    """An engine that feeds a decode step the wrong position fails phase 3
    on its own bookkeeping check, before any tolerance is consulted."""
    from repro.serving.engine import ContinuousBatchingEngine
    admit = ContinuousBatchingEngine._admit

    def off_by_one(self, req, slot):
        admit(self, req, slot)
        self._slot_pos[slot] += 1
    monkeypatch.setattr(ContinuousBatchingEngine, "_admit", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match="decode fed"):
        chip_smoke.phase_reference(reduced=True)


def _run(script: Path, cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    r = _run(SCRIPT, ROOT, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_exits_nonzero_alone(tmp_path):
    """Copied out of the checkout, the script cannot import the program."""
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(tmp_path / SCRIPT.name, tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
