"""The engine's own spans on a half-second trace of
``olmo-1b.readout-steady`` recorded on a TPU v5e (``data/``): the device's
idle time inside each ``serving.step`` falls in the step's named child
spans, and the device trace names the step programs."""
import bisect
import gzip
import re
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data" / "olmo_readout_spans_0.5s.xplane.pb.gz"
CHILDREN = ("serving.schedule", "serving.admit", "serving.decode.launch",
            "serving.decode.wait", "serving.retire")


@pytest.fixture(scope="module")
def planes():
    return tr.load(gzip.decompress(DATA.read_bytes()))


def _host(planes, *names) -> list:
    return sorted((ev.start, ev.end) for pname, lines in planes.items()
                  if pname.startswith("/host") for evs in lines.values()
                  for ev in evs if ev.name in names)


def _busy(planes) -> list:
    """Union of the device's operation intervals."""
    (lines,) = [l for n, l in planes.items() if tr.DEVICE_RE.match(n)]
    return tr._union((ev.start, ev.end) for ev in lines["XLA Ops"])


def _idle(busy, starts, a, b) -> list:
    """The parts of ``[a, b]`` in which no device operation runs."""
    out, t = [], a
    for s, e in busy[max(0, bisect.bisect_right(starts, a) - 1):]:
        if s >= b:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def _covered(gaps, spans) -> float:
    return sum(max(0.0, min(b, e) - max(a, s))
               for a, b in gaps for s, e in spans if s < b and e > a)


def test_device_idle_in_a_step_falls_in_its_named_parts(planes):
    (window,) = _host(planes, "bench.window")
    steps = [s for s in _host(planes, "serving.step")
             if window[0] <= s[0] < window[1]]
    children = _host(planes, *CHILDREN)
    busy = _busy(planes)
    starts = [s for s, _ in busy]
    idle = covered = 0.0
    for a, b in steps:
        gaps = _idle(busy, starts, a, b)
        idle += sum(e - s for s, e in gaps)
        k = bisect.bisect_left(children, (a, a))
        mine = []
        while k < len(children) and children[k][0] < b:
            mine.append(children[k])
            k += 1
        covered += _covered(gaps, mine)
    assert len(steps) > 10 and idle > 0
    assert covered >= 0.9 * idle, (covered, idle)


def test_device_names_the_step_programs(planes):
    (lines,) = [l for n, l in planes.items() if tr.DEVICE_RE.match(n)]
    names = {re.sub(r"\(\d+\)$", "", ev.name) for ev in lines["XLA Modules"]}
    assert {"jit_prefill_into_slot_step", "jit_decode_step"} <= names
    assert "jit__unknown" not in names
