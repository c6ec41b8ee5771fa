"""Open-loop camera-frame traffic from a mix file and a seed.

A mix (``bench/traffic/<mix>.json``) gives the prompt length, the range of
output lengths, the deadline, and a repeating list of phases, each a length
in seconds and the frame rate every camera keeps during it. The cell file
(``bench/cells/<cell>.json``) gives the number of cameras.

Frame times follow the fps arithmetic of ``repro.serving.StreamSimulator``
(each camera owes ``fps * dt`` frames over ``dt`` and emits one whenever that
running count crosses a whole number), taken in continuous time: camera
``c`` emits frame ``k`` when its count reaches ``k + phase_c``. The phases
are uniform draws, as uncoordinated cameras have, so arrivals bunch; they
are drawn once per number of cameras and not from the seed, which only
deals them to the cameras in another order, so every seed offers the same
arrival times. Output lengths run through the range once per block of
``hi - lo + 1`` frames, in a seed-drawn order, so every seed offers the
same work in another order.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Frame:
    index: int
    camera: int
    due_s: float            # seconds after the window opens
    tokens: np.ndarray      # (prompt_tokens,) int32
    new_tokens: int
    deadline_s: float


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one use of the seed; any non-negative integer seed."""
    words = [int(b) for b in stream.encode()]
    return np.random.default_rng(np.random.SeedSequence([int(seed), *words]))


def peak_fps(mix: dict) -> float:
    return max(p["fps"] for p in mix["phases"])


def steady(mix: dict) -> dict:
    """The mix with every camera held at its peak rate (for knee sweeps)."""
    return {**mix, "phases": [{"seconds": 1.0, "fps": peak_fps(mix)}]}


def arrival_times(mix: dict, cameras: int, seconds: float) -> np.ndarray:
    """(cameras, k) due times in [0, seconds), NaN-padded, camera i at a
    uniform phase of its frame count, the same draw for every seed."""
    period = sum(p["seconds"] for p in mix["phases"])
    n_periods = int(np.ceil(seconds / period)) + 1
    t, cum = [0.0], [0.0]
    for _ in range(n_periods):
        for p in mix["phases"]:
            t.append(t[-1] + p["seconds"])
            cum.append(cum[-1] + p["seconds"] * p["fps"])
    t, cum = np.asarray(t), np.asarray(cum)
    n_max = int(np.ceil(cum[-1])) + 1
    phase = rng_for(cameras, "phases").random(cameras)
    counts = np.arange(n_max)[None, :] + phase[:, None]
    due = np.interp(counts, cum, t)
    return np.where(due < seconds, due, np.nan)


def frames(mix: dict, cameras: int, seconds: float, seed: int,
           vocab: int) -> list[Frame]:
    """Every frame due in ``[0, seconds)``, in due order."""
    due = arrival_times(mix, cameras, seconds)
    order = rng_for(seed, "cameras").permutation(cameras)
    cam, k = np.nonzero(~np.isnan(due))
    times = due[cam, k]
    idx = np.lexsort((cam, times))
    lo, hi = mix["output_tokens"]
    block = hi - lo + 1
    n = len(idx)
    rng = rng_for(seed, "lengths")
    lengths = np.concatenate([lo + rng.permutation(block)
                              for _ in range(-(-n // block))])[:n]
    prompts = rng_for(seed, "prompts").integers(
        0, vocab, (n, mix["prompt_tokens"]), dtype=np.int32)
    return [Frame(index=j, camera=int(order[cam[i]]), due_s=float(times[i]),
                  tokens=prompts[j], new_tokens=int(lengths[j]),
                  deadline_s=float(mix["deadline_s"]))
            for j, i in enumerate(idx)]
