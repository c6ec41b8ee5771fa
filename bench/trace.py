"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s:
``bench.window`` around the measured window, ``driver.submit``,
``engine.step``, ``driver.idle``, and ``program.prefill`` /
``program.decode`` around each call of the engine's two compiled programs,
with the call's index as the span's ``call`` argument. The traced run
wraps those calls and blocks on their results inside the span.

Device planes are ``/device:TPU:<n>``: program launches on the
``XLA Modules`` line, operations on ``XLA Ops``. Both step programs carry
the module name ``jit__unknown`` (each is a ``jax.jit`` of a
``functools.partial``) but different fingerprints in parentheses, so a
module name is tied to a program by the calls whose spans its launches
overlap most (the device clock sits within about a millisecond of the
host's, which calls of several milliseconds outlast), and every launch of
that module name is then that program's. An operation belongs to the
launch whose interval holds its start. A kernel is a ``tpu_custom_call``
operation named after the Pallas kernel (``%flash_attention.6 = ...``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import re

SPANS = ("bench.window", "driver.submit", "driver.idle", "engine.step",
         "program.prefill", "program.decode")
DEVICE_RE = re.compile(r"^/device:TPU:\d+$")
KERNEL_RE = re.compile(r'^%([A-Za-z_]\w*?)(?:\.\d+)? = .*'
                       r'custom_call_target="tpu_custom_call"')
OP_RE = re.compile(r"^(%\S+) = (.*?)\s*(\w[\w\-]*)\(")
LAYOUT_RE = re.compile(r"\{[^{}]*\}")
CONTAINERS = ("while", "conditional", "call")
TOP = 10


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds on the trace's clock
    end: float
    stats: dict


@dataclasses.dataclass
class Call:
    kind: str
    start: float
    end: float
    device_s: float = 0.0
    launches: int = 0
    kernel_s: dict = dataclasses.field(default_factory=dict)
    kernel_n: dict = dataclasses.field(default_factory=dict)


def _events(line, with_stats: bool) -> list:
    out = []
    for ev in line.events:
        stats = {}
        if with_stats:
            stats = {k: v for k, v in ev.stats}
        s = ev.start_ns * 1e-9
        out.append(Event(ev.name, s, s + ev.duration_ns * 1e-9, stats))
    return out


def load(path_or_bytes) -> dict:
    """Planes of a trace: ``{plane: {line: [Event]}}``; arguments are read
    for host events only."""
    from jax.profiler import ProfileData
    if isinstance(path_or_bytes, (bytes, bytearray)):
        pd = ProfileData.from_serialized_xspace(bytes(path_or_bytes))
    else:
        pd = ProfileData.from_file(str(path_or_bytes))
    return {plane.name: {line.name: _events(line, plane.name.startswith("/host"))
                         for line in plane.lines}
            for plane in pd.planes}


def find_xplane(directory: str) -> str | None:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@functools.lru_cache(maxsize=None)
def short_name(op: str) -> str:
    """``%copy.61 = bf16[16,32,512,16,128] copy`` from an operation's HLO."""
    text = LAYOUT_RE.sub("", op)
    m = OP_RE.match(text)
    return f"{m.group(1)} = {m.group(2)} {m.group(3)}"[:160] if m else text[:160]


@functools.lru_cache(maxsize=None)
def _op_type(op: str) -> str:
    m = OP_RE.match(LAYOUT_RE.sub("", op))
    return m.group(3) if m else ""


@functools.lru_cache(maxsize=None)
def _kernel(op: str) -> str | None:
    m = KERNEL_RE.match(op)
    return m.group(1) if m else None


def _best_overlap(starts, spans, a, b):
    """Index into ``spans`` of the span overlapping ``[a, b]`` most."""
    k = bisect.bisect_right(starts, b)
    best, best_ov = None, 0.0
    for j in range(max(0, k - 3), min(len(spans), k + 1)):
        s, e = spans[j][0], spans[j][1]
        ov = min(b, e) - max(a, s)
        if ov > best_ov:
            best, best_ov = j, ov
    return best


def reduce(planes: dict) -> dict | None:
    """Busy time, idle gaps, per-call device time, the time and count of
    every kernel in each call by its name, and the top device operations
    inside the ``bench.window`` span. ``None`` where the trace has no such
    span or no device plane."""
    host, calls, window = [], {}, None
    for pname, lines in planes.items():
        if not pname.startswith("/host"):
            continue
        for events in lines.values():
            for ev in events:
                if ev.name not in SPANS:
                    continue
                if ev.name == "bench.window":
                    window = (ev.start, ev.end)
                    continue
                host.append((ev.start, ev.end, ev.name))
                if "call" in ev.stats:
                    calls[int(ev.stats["call"])] = Call(
                        ev.name.split(".")[1], ev.start, ev.end)
    devices = {n: lines for n, lines in planes.items() if DEVICE_RE.match(n)}
    if window is None or not devices:
        return None
    lo, hi = window
    host.sort()
    spans = sorted((c.start, c.end, i) for i, c in calls.items())
    starts = [s[0] for s in spans]

    busy, gaps, ops_by = [], [], collections.Counter()
    for lines in devices.values():
        mods = lines.get("XLA Modules", [])
        ops = lines.get("XLA Ops", [])
        # which module name is which program: each call votes for the
        # launch that overlaps its span most
        owner = [None] * len(mods)
        top = {}
        for k, ev in enumerate(mods):
            j = _best_overlap(starts, spans, ev.start, ev.end)
            if j is None:
                continue
            i = spans[j][2]
            owner[k] = i
            ov = min(ev.end, spans[j][1]) - max(ev.start, spans[j][0])
            if i not in top or ov > top[i][0]:
                top[i] = (ov, ev.name)
        votes = collections.defaultdict(collections.Counter)
        for i, (_, name) in top.items():
            votes[name][calls[i].kind] += 1
        program = {name: v.most_common(1)[0][0] for name, v in votes.items()
                   if v.most_common(1)[0][1] >= 0.5 * sum(v.values())}
        launch_kind = []
        for ev, i in zip(mods, owner):
            kind = program.get(ev.name)
            mine = kind is not None and i is not None and calls[i].kind == kind
            launch_kind.append((kind, i if mine else None))
            if mine:
                calls[i].device_s += ev.end - ev.start
                calls[i].launches += 1
        mod_starts = [ev.start for ev in mods]
        for op in ops:
            k = bisect.bisect_right(mod_starts, op.start) - 1
            kind, i = (launch_kind[k] if k >= 0 and op.start <= mods[k].end
                       else (None, None))
            if lo <= op.start < hi and _op_type(op.name) not in CONTAINERS:
                ops_by[f"{kind or 'other'}: {short_name(op.name)}"] += \
                    op.end - op.start
            kernel = _kernel(op.name)
            if kernel is not None and i is not None:
                c = calls[i]
                c.kernel_s[kernel] = c.kernel_s.get(kernel, 0.0) \
                    + op.end - op.start
                c.kernel_n[kernel] = c.kernel_n.get(kernel, 0) + 1
        u = _union((max(e.start, lo), min(e.end, hi)) for e in (ops or mods)
                   if e.end > lo and e.start < hi)
        busy.append(sum(b - a for a, b in u))
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        gaps += [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                 if edges[j + 1] > edges[j]]

    def label(a, b):
        """The innermost host span open at the gap's middle."""
        mid, best = 0.5 * (a + b), None
        for s, e, name in host:
            if s > mid:
                break
            if e >= mid:
                best = name
        return best or "outside spans"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "calls": {i: c for i, c in calls.items() if lo <= c.start < hi},
        "device_ops": [[k, v] for k, v in ops_by.most_common(TOP)],
        "idle_gaps": [[label(a, b), b - a] for a, b in gaps[:TOP]],
    }
