"""The open-loop driver: runs in the engine's own process, submits every
frame whose due time has passed, then calls ``engine.step()``; sleeps until
the next due time when nothing is queued or active. Frames are timed from
their due time, not from when they were submitted, so a step that stalls
the loop counts against every frame that fell due meanwhile.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Optional

from repro.serving import Request

from bench.traffic import Frame


@dataclasses.dataclass
class Record:
    frame: Frame
    request: Request
    due_t: float            # monotonic clock
    submit_t: float = float("nan")
    admit_t: float = float("nan")   # filled by the traced run's tap

    @property
    def finished(self) -> bool:
        return self.request.output is not None

    @property
    def latency_s(self) -> float:
        return self.request.finish_t - self.due_t


def _nospan(name: str):
    return contextlib.nullcontext()


def drive(engine, frames: list[Frame], seconds: float, *,
          drain_cap_s: float = 60.0,
          span: Callable = _nospan,
          t0: Optional[float] = None) -> tuple[list[Record], float]:
    """Offer ``frames`` for ``seconds`` from ``t0`` (default: now), then
    stop offering and drain for at most ``drain_cap_s`` past the close.
    Returns the records of every frame due in the window, and ``t0``."""
    t0 = time.monotonic() if t0 is None else t0
    recs = [Record(f, Request(f"f{f.index}-c{f.camera}", f.tokens,
                              max_new_tokens=f.new_tokens,
                              stream_id=f"cam-{f.camera}",
                              deadline_s=f.deadline_s), t0 + f.due_s)
            for f in frames if f.due_s < seconds]
    close, nxt = t0 + seconds, 0

    def submit_due(now: float) -> None:
        nonlocal nxt
        if nxt < len(recs) and recs[nxt].due_t <= now:
            with span("driver.submit"):
                while nxt < len(recs) and recs[nxt].due_t <= now:
                    recs[nxt].submit_t = time.monotonic()
                    engine.submit(recs[nxt].request)
                    nxt += 1

    def busy() -> bool:
        return bool(engine.queue) or bool(engine.active_slots())

    window = span("bench.window")
    window.__enter__()
    while True:
        now = time.monotonic()
        if now >= close:
            window.__exit__(None, None, None)
            break
        submit_due(now)
        if busy():
            with span("engine.step"):
                engine.step()
        else:
            wake = min(recs[nxt].due_t if nxt < len(recs) else close, close)
            with span("driver.idle"):
                time.sleep(max(0.0, wake - time.monotonic()))
    submit_due(close)                       # due in the window, not yet sent
    cap = close + drain_cap_s
    while busy() and time.monotonic() < cap:
        with span("engine.step"):
            engine.step()
    return recs, t0
