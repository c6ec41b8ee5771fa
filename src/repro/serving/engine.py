"""Serving engines + camera-stream simulator.

The paper's workload is "analysis program x camera stream at a frame rate".
The modern analogue served here: each camera frame becomes one fixed-size
inference request (frame caption / detection readout from a VLM-style
decoder); a stream at f fps enqueues f requests per second.

Two engines (see DESIGN.md for the design rationale):

* ``ServingEngine`` — static lock-step batching: prefill a batch of
  equal-length prompts, then decode all of them together; the batch stalls
  until its slowest request finishes.
* ``ContinuousBatchingEngine`` — a fixed pool of preallocated KV-cache
  slots; new requests are admitted into free slots mid-decode (single-slot
  prefill-into-cache, no re-prefill of the pool), finished requests free
  their slot immediately, and the queue is drained earliest-deadline-first
  using each stream's per-frame latency budget (1/fps).

The measured tokens/sec feeds core/tpu_catalog.py, which runs the paper's
packing machinery over TPU slice types instead of EC2 instances.

The continuous engine instruments itself. Host spans
(``jax.profiler.TraceAnnotation``, recorded only while a profiler trace
runs, so they share the device trace's clock) mark each part of a step::

    serving.step (queued, active)
      serving.schedule                 EDF sort, choice of free slots
      serving.admit (request_id, slot, prompt_tokens)
        serving.prefill.launch         device inputs, program call, the
                                       first token into the token vector
      serving.decode.launch (active)   device inputs, program call, argmax
      serving.decode.wait              earlier programs' tokens to the host
      serving.retire (request_id)
    host.gc (generation)               a Python garbage collection

and always-on counters, under ``report()["host"]``,
``report()["pipeline"]`` and ``report()["cache"]``, sum the same phases,
count the read-backs, and count the program calls that updated the slot
cache in place.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
import weakref
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.ops import on_tpu
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.models.steps import (make_jitted_decode, make_jitted_prefill,
                                make_jitted_prefill_into_slot)


# Programs the continuous engine keeps dispatched and unread: how far the
# host may run ahead of the device. Each program is donated the cache and
# updates it in place, so a program in flight holds no cache of its own;
# two queued behind a running one keep the device busy.
MAX_IN_FLIGHT = 3


@jax.jit
def _greedy_tokens(logits):
    """A decode step's greedy tokens: (slots, V) logits -> (slots,) int32,
    the token vector that feeds the next decode."""
    return jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def _seat_first_token(tokens, logits, slot):
    """An admission's first token, greedy over its prefill's (V,) logits,
    written into row ``slot`` of the token vector that feeds the next
    decode. Returns (that vector, the token as a (1,) array)."""
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return tokens.at[slot].set(first), first[None]


def serving_options() -> M.ModelOptions:
    """Default execution options of both engines: no remat, and the
    architecture's Pallas kernels on a TPU backend (the ``jnp`` reference
    path everywhere else)."""
    return M.ModelOptions(remat=False, use_kernels=on_tpu())


@dataclasses.dataclass
class Request:
    request_id: str
    tokens: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    stream_id: Optional[str] = None
    enqueue_t: float = 0.0
    deadline_s: float = float("inf")   # per-frame latency budget (1/fps)
    output: Optional[np.ndarray] = None
    finish_t: float = 0.0
    # continuous engine, same clock as enqueue_t; nan until they happen
    admit_t: float = float("nan")        # admission starts
    first_token_t: float = float("nan")  # the prefill's token on the host

    @property
    def deadline_t(self) -> float:
        return self.enqueue_t + self.deadline_s

    @property
    def latency_s(self) -> float:
        return self.finish_t - self.enqueue_t


class _EngineStatsMixin:
    """Shared stats accounting (both engines keep a ``stats`` dict with a
    float ``wall_s`` and integer counters including ``tokens_generated``,
    plus per-stream token tallies and active windows behind
    ``measured_rates``/``windowed_rates``)."""

    def _init_stream_stats(self) -> None:
        self._stream_tokens: dict[str, int] = {}
        # per-stream active window [first_seen, last_seen] on the engine
        # clock (cumulative wall_s): a late joiner's window starts at the
        # step that first served it, an early leaver's ends at its last
        self._stream_window: dict[str, list[float]] = {}
        self._touched: set[str] = set()
        self._rate_snapshot: tuple[float, dict[str, int]] = (0.0, {})

    def reset_stats(self) -> None:
        """Zero the counters (e.g. after a jit warmup run)."""
        self.stats = {k: 0.0 if isinstance(v, float) else 0
                      for k, v in self.stats.items()}
        self._init_stream_stats()

    def throughput_tokens_per_s(self) -> float:
        if self.stats["wall_s"] == 0:
            return 0.0
        return self.stats["tokens_generated"] / self.stats["wall_s"]

    def _count_stream_token(self, req: Request, n: int = 1) -> None:
        key = req.stream_id or req.request_id
        self._stream_tokens[key] = self._stream_tokens.get(key, 0) + n
        self._touched.add(key)

    def _mark_windows(self, clock0: float, clock1: float) -> None:
        """Extend the active window of every stream served this step to
        cover [clock0, clock1] (engine-clock seconds)."""
        for key in self._touched:
            w = self._stream_window.get(key)
            if w is None:
                self._stream_window[key] = [clock0, clock1]
            elif clock1 > w[1]:
                w[1] = clock1
        self._touched.clear()

    def measured_rates(self) -> dict[str, float]:
        """Measured tokens/sec per stream over *that stream's* active window
        (first-seen to last-seen on the engine clock).

        This is the profiling export the paper's manager consumes: feed it to
        ``core.tpu_catalog.streams_from_measured`` (or ``streams_from_engine``)
        to build packing items from observed — not nominal — throughput, and
        to the fleet simulator's ``ServiceCalibration`` to bound how many
        frames a simulated instance can actually analyze.

        Per-stream windows matter: dividing by the engine's *total* wall time
        systematically under-measures streams that join late or leave early
        — a drift detector fed such rates chases phantom throughput drops.
        A stream whose window is empty (all tokens in one step on a clock
        that did not advance) falls back to the total wall time.
        """
        wall = self.stats["wall_s"]
        out: dict[str, float] = {}
        for sid, n in sorted(self._stream_tokens.items()):
            w = self._stream_window.get(sid)
            span = (w[1] - w[0]) if w is not None else 0.0
            if span <= 0.0:
                span = wall
            if span <= 0.0:
                continue
            out[sid] = n / span
        return out

    def windowed_rates(self) -> dict[str, float]:
        """Tokens/sec per stream since the *previous* call (poll-style
        window over the cumulative counters).

        This is the live telemetry export a drift detector should consume:
        lifetime averages (``measured_rates``) dilute a throughput
        regression across the whole history, while successive windows show
        it at full magnitude immediately. Streams with no tokens in the
        window are omitted (no data, not zero throughput)."""
        wall = self.stats["wall_s"]
        prev_wall, prev_tokens = self._rate_snapshot
        span = wall - prev_wall
        out: dict[str, float] = {}
        if span > 0:
            for sid, n in sorted(self._stream_tokens.items()):
                delta = n - prev_tokens.get(sid, 0)
                if delta > 0:
                    out[sid] = delta / span
        self._rate_snapshot = (wall, dict(self._stream_tokens))
        return out


class _GcPauses:
    """``gc.callbacks`` hook: a ``host.gc`` span around every collection
    of the process, and its pause counted by every live engine that
    watches. Installed once per process, by the first engine."""

    def __init__(self):
        self.engines: weakref.WeakSet = weakref.WeakSet()
        self._open: Optional[tuple[float, TraceAnnotation]] = None

    def watch(self, engine) -> None:
        if self not in gc.callbacks:
            gc.callbacks.append(self)
        self.engines.add(engine)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            span = TraceAnnotation("host.gc", generation=info["generation"])
            span.__enter__()
            self._open = (time.monotonic(), span)
        elif self._open is not None:
            t0, span = self._open
            self._open = None
            span.__exit__(None, None, None)
            pause = time.monotonic() - t0
            for eng in self.engines:
                eng._count_gc(pause)


_GC_PAUSES = _GcPauses()


class ServingEngine(_EngineStatsMixin):
    """Static-batching engine for equal-length frame requests."""

    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 cache_len: int = 512, opts: Optional[M.ModelOptions] = None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.opts = opts or serving_options()
        self.queue: list[Request] = []
        self._prefill = make_jitted_prefill(cfg, self.opts, cache_len)
        self._decode = make_jitted_decode(cfg, self.opts)
        self._init_stream_stats()
        self.stats = {"requests": 0, "tokens_generated": 0, "batches": 0,
                      "decode_steps": 0, "wall_s": 0.0}

    def submit(self, req: Request) -> None:
        req.enqueue_t = time.monotonic()
        self.queue.append(req)

    def _pad_batch(self, reqs: Sequence[Request]) -> jnp.ndarray:
        L = max(len(r.tokens) for r in reqs)
        assert all(len(r.tokens) == L for r in reqs), \
            "static batching requires equal-length frame requests"
        toks = np.stack([r.tokens for r in reqs])
        return jnp.asarray(toks, jnp.int32)

    def step(self) -> list[Request]:
        """Serve one batch from the queue; returns completed requests."""
        if not self.queue:
            return []
        batch_reqs = self.queue[: self.max_batch]
        self.queue = self.queue[len(batch_reqs):]
        t0 = time.monotonic()
        clock0 = self.stats["wall_s"]

        tokens = self._pad_batch(batch_reqs)
        B, L = tokens.shape
        logits, cache = self._prefill(self.params, {"tokens": tokens})
        max_new = max(r.max_new_tokens for r in batch_reqs)
        outs = np.zeros((B, max_new), np.int32)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(max_new):
            outs[:, i] = np.asarray(tok)
            logits, cache = self._decode(self.params, cache,
                                         {"token": tok,
                                          "pos": jnp.asarray(L + i, jnp.int32)})
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            self.stats["decode_steps"] += 1

        wall = time.monotonic() - t0
        self.stats["wall_s"] += wall
        self.stats["batches"] += 1
        for b, r in enumerate(batch_reqs):
            r.output = outs[b, : r.max_new_tokens]
            r.finish_t = time.monotonic()
            self.stats["requests"] += 1
            self.stats["tokens_generated"] += r.max_new_tokens
            self._count_stream_token(r, r.max_new_tokens)
        self._mark_windows(clock0, self.stats["wall_s"])
        return list(batch_reqs)

    def drain(self) -> list[Request]:
        done: list[Request] = []
        while self.queue:
            done.extend(self.step())
        return done

class ContinuousBatchingEngine(_EngineStatsMixin):
    """Continuous batching over a fixed pool of preallocated KV-cache slots.

    Each of the ``max_slots`` rows of one batched cache (length ``cache_len``)
    is a slot. Per step: (1) admit queued requests into free slots in
    earliest-deadline-first order — each admission prefills that one request
    and inserts its KV/state into the slot (steps.prefill_into_slot_step),
    leaving the other slots' caches untouched; (2) dispatch a single batched
    decode step with per-slot positions, and free the slot of every request
    whose last token that step computes, for the next admission instead of
    stalling until the whole batch drains; (3) read the tokens of the
    programs dispatched in earlier steps to the host, and retire the
    requests whose last token arrived.

    The tokens that feed the next decode stay on the device: the decode's
    argmax writes them, and each admission writes its first token into its
    slot's row. The host schedules by counts (positions, ``max_new_tokens``)
    and never needs a token's value to dispatch, so it reads one step
    behind while the device works through the next. A step that leaves no
    slot active and nothing queued reads back everything still in flight,
    so every request submitted has its ``output`` once the engine is idle.
    Before it dispatches a program, the host reads back the oldest while
    ``MAX_IN_FLIGHT`` are unread, which bounds how far the host runs ahead
    of the device in a burst of admissions.

    Both programs are donated the slot cache: a prefill writes only its
    slot and a decode only each slot's new K/V row (or each state mixer's
    state), in the same buffers, with no copy of the rest of the cache.

    Greedy decoding is identical to the static engine's: the prefill's
    last-position argmax is the first generated token, and each decode step
    at position prompt_len + i yields token i + 1. (Exception: capacity-
    limited MoE routing is batch-global — tokens compete for expert capacity
    with whatever shares the batch — so MoE outputs depend on batch
    composition under either engine; per-request token equality holds for
    the batch-independent mixers: dense/windowed attention, SSD, RG-LRU.)
    """

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 8,
                 cache_len: int = 512, opts: Optional[M.ModelOptions] = None):
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.opts = opts or serving_options()
        self.queue: list[Request] = []
        # the cache is the programs' argument 1, handed over on every call
        self._prefill_slot = make_jitted_prefill_into_slot(
            cfg, self.opts, cache_len, donate_argnums=1)
        self._decode = make_jitted_decode(cfg, self.opts, donate_argnums=1)
        dtype = jax.tree.leaves(params)[0].dtype
        self.cache = M.init_cache(cfg, max_slots, cache_len, dtype, self.opts)
        self._cache_bytes = sum(a.nbytes for a in jax.tree.leaves(self.cache))
        self._slot_req: list[Optional[Request]] = [None] * max_slots
        self._slot_pos = np.zeros(max_slots, np.int32)   # next write position
        # the occupant's tokens on the host, and its tokens not yet dispatched
        self._slot_out: list[list[int]] = [[] for _ in range(max_slots)]
        self._slot_left = [0] * max_slots
        # next token to feed each slot, on the device
        self._next = jnp.zeros(max_slots, jnp.int32)
        # programs whose tokens are not yet on the host, oldest first: their
        # token array and (row, request, the request's host tokens) per row
        self._inflight: list[tuple[jax.Array, list]] = []
        self._latencies: list[float] = []
        self._slo_hits = 0
        self._occupancy_sum = 0.0
        self._init_stream_stats()
        self.stats = {"requests": 0, "tokens_generated": 0, "prefills": 0,
                      "decode_steps": 0, "wall_s": 0.0}
        self._zero_host()
        _GC_PAUSES.watch(self)

    # -- queue ---------------------------------------------------------------

    def submit(self, req: Request) -> None:
        if len(req.tokens) + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request {req.request_id}: prompt {len(req.tokens)} + "
                f"{req.max_new_tokens} new tokens exceeds cache_len "
                f"{self.cache_len}")
        req.enqueue_t = time.monotonic()
        self.queue.append(req)

    def active_slots(self) -> list[int]:
        return [s for s in range(self.max_slots)
                if self._slot_req[s] is not None]

    # -- engine loop ---------------------------------------------------------

    @contextlib.contextmanager
    def _phase(self, counter: str, name: str, **args):
        """A host span named ``name`` whose duration is added to the host
        counter ``counter``."""
        t0 = time.monotonic()
        with TraceAnnotation(name, **args):
            yield
        self._host[counter] += time.monotonic() - t0

    def _enqueue_read(self, tokens: jax.Array, rows: list) -> None:
        """Keep a dispatched program's token array until its read-back, and
        start its copy to the host."""
        tokens.copy_to_host_async()
        self._inflight.append((tokens, rows))

    def _admit(self, req: Request, slot: int) -> None:
        req.admit_t = time.monotonic()
        with TraceAnnotation("serving.admit", request_id=req.request_id,
                             slot=slot, prompt_tokens=len(req.tokens)):
            with self._phase("launch_s", "serving.prefill.launch"):
                tokens = jnp.asarray(req.tokens[None, :], jnp.int32)
                at = jnp.asarray(slot, jnp.int32)
                handed = self.cache
                logits, self.cache = self._prefill_slot(
                    self.params, handed, {"tokens": tokens}, at)
                self._count_cache(handed)
                self._next, first = _seat_first_token(self._next, logits, at)
                out: list[int] = []
                self._enqueue_read(first, [(0, req, out)])
            self._slot_req[slot] = req
            self._slot_out[slot] = out
            self._slot_left[slot] = req.max_new_tokens - 1
            self._slot_pos[slot] = len(req.tokens)
            self.stats["prefills"] += 1

    def _count_cache(self, handed) -> None:
        """Count a program call, and whether it consumed the cache it was
        handed: every leaf deleted, so the program updates it in place."""
        self._cache["calls"] += 1
        self._cache["in_place"] += all(a.is_deleted()
                                       for a in jax.tree.leaves(handed))

    def _read_back(self, keep: int, overlapped: bool) -> list[Request]:
        """Bring the tokens of all but the newest ``keep`` programs in
        flight to the host, oldest first; returns the requests whose last
        token arrived. ``overlapped``: a later program is already
        dispatched, so the device stays busy while the host waits here."""
        n = len(self._inflight) - keep
        if n <= 0:
            return []
        entries = self._inflight[:n]
        del self._inflight[:n]
        self._pipeline["readbacks"] += 1
        self._pipeline["overlapped" if overlapped else "flushes"] += 1
        done = []
        for tokens, rows in entries:
            with self._phase("wait_s", "serving.decode.wait"):
                vals = np.asarray(tokens)
            arrived = time.monotonic()
            for row, req, out in rows:
                out.append(int(vals[row]))
                if len(out) == 1:
                    req.first_token_t = arrived
                self.stats["tokens_generated"] += 1
                self._count_stream_token(req)
                if len(out) == req.max_new_tokens:
                    done.append(self._retire(req, out))
        return done

    def _retire(self, req: Request, out: list[int]) -> Request:
        with TraceAnnotation("serving.retire", request_id=req.request_id):
            req.output = np.asarray(out, np.int32)
            req.finish_t = time.monotonic()
            self._latencies.append(req.latency_s)
            if req.latency_s <= req.deadline_s:
                self._slo_hits += 1
            self.stats["requests"] += 1
        return req

    def step(self) -> list[Request]:
        """One engine iteration: EDF admission into free slots, one batched
        decode step for every occupied slot, then the read-back of the
        programs of earlier steps. Returns the requests whose last token
        reached the host in this call."""
        t0 = time.monotonic()
        clock0 = self.stats["wall_s"]
        done: list[Request] = []
        dispatched = 0
        with TraceAnnotation("serving.step", queued=len(self.queue),
                             active=len(self.active_slots())):
            # 1) admission, earliest deadline first
            with TraceAnnotation("serving.schedule"):
                self.queue.sort(key=lambda r: r.deadline_t)
                free = [s for s in range(self.max_slots)
                        if self._slot_req[s] is None]
                admit = list(zip(free, self.queue))
                del self.queue[:len(admit)]
            for slot, req in admit:
                done += self._read_back(MAX_IN_FLIGHT - 1, True)
                self._admit(req, slot)
                dispatched += 1
                if not self._slot_left[slot]:       # max_new_tokens == 1
                    self._slot_req[slot] = None

            # 2) one decode step for all active slots (free slots ride along
            # and are overwritten by the next admission's prefill); a slot
            # is free once its last token's program is dispatched
            active = self.active_slots()
            if active:
                done += self._read_back(MAX_IN_FLIGHT - 1, True)
                dispatched += 1
                with self._phase("launch_s", "serving.decode.launch",
                                 active=len(active)):
                    # a copy: on the CPU the device array may alias its
                    # host buffer, and the positions advance before the
                    # step runs
                    pos = jnp.asarray(self._slot_pos.copy())
                    handed = self.cache
                    logits, self.cache = self._decode(
                        self.params, handed, {"token": self._next, "pos": pos})
                    self._count_cache(handed)
                    self._next = _greedy_tokens(logits)
                    self._enqueue_read(self._next, [
                        (s, self._slot_req[s], self._slot_out[s])
                        for s in active])
                self.stats["decode_steps"] += 1
                self._occupancy_sum += len(active) / self.max_slots
                for s in active:
                    self._slot_pos[s] += 1
                    self._slot_left[s] -= 1
                    if not self._slot_left[s]:
                        self._slot_req[s] = None

            # 3) read back the programs of earlier steps, while this step's
            # programs keep the device busy; all of them when it goes idle
            idle = not self.queue and not self.active_slots()
            done += self._read_back(0 if idle else dispatched, not idle)

        self.stats["wall_s"] += time.monotonic() - t0
        self._mark_windows(clock0, self.stats["wall_s"])
        self._host["steps"] += 1
        return done

    def drain(self) -> list[Request]:
        done: list[Request] = []
        while self.queue or self.active_slots():
            done.extend(self.step())
        return done

    # -- reporting -----------------------------------------------------------

    def reset_stats(self) -> None:
        """Zero the counters and latency records (e.g. after a jit warmup)."""
        super().reset_stats()
        self._latencies = []
        self._slo_hits = 0
        self._occupancy_sum = 0.0
        self._zero_host()

    def _zero_host(self) -> None:
        self._host = {"steps": 0, "launch_s": 0.0, "wait_s": 0.0,
                      "gc_pauses": 0, "gc_pause_s": 0.0,
                      "gc_pause_max_s": 0.0}
        self._pipeline = {"readbacks": 0, "overlapped": 0, "flushes": 0}
        self._cache = {"calls": 0, "in_place": 0}

    def _count_gc(self, pause: float) -> None:
        h = self._host
        h["gc_pauses"] += 1
        h["gc_pause_s"] += pause
        h["gc_pause_max_s"] = max(h["gc_pause_max_s"], pause)

    def report(self) -> dict:
        """SLO attainment, latency percentiles, and slot occupancy — the
        scheduler-facing metrics (tokens/s feeds the packing catalog).

        With no completed requests yet the latency fields *and*
        ``slo_attainment`` are ``None`` (there is no percentile — nor an
        attainment fraction — of an empty sample; reporting 1.0 would feed
        a drift detector "perfect SLO" from an idle engine) and the
        counters are zero — the report never raises. Contrast with
        ``Ledger.slo_attainment()``, which is vacuously 1.0 only under
        zero *demand* (nothing was asked for, so nothing was missed).

        ``host`` holds the host counters since the last ``reset_stats()``:
        ``steps`` and ``step_s`` (wall time inside ``step()``, the
        ``wall_s`` of ``stats``), ``launch_s``
        (building inputs and calling a program until the call returns),
        ``wait_s`` (blocking until earlier programs' tokens are on the
        host), ``host_s`` = ``step_s - launch_s - wait_s`` (scheduling,
        bookkeeping, retirement), and the process's garbage collections:
        ``gc_pauses``, ``gc_pause_s``, ``gc_pause_max_s``.

        ``pipeline`` counts the read-backs since the last ``reset_stats()``:
        ``readbacks``, of which ``overlapped`` (a later program was already
        dispatched, so the device had work while the host waited) and
        ``flushes`` (the engine went idle and read everything in flight).

        ``cache`` holds ``bytes``, the slot pool's cache, and counts the
        program calls since the last ``reset_stats()``: ``calls`` (prefills
        and decodes) and ``in_place``, those that consumed the cache handed
        to them (donated, so written in place rather than copied).
        """
        lat = sorted(self._latencies)
        n = len(lat)

        def pct(p: float) -> Optional[float]:
            if not lat:
                return None
            return lat[min(n - 1, max(0, int(np.ceil(p * n)) - 1))]

        steps = self.stats["decode_steps"]
        return {
            "requests": self.stats["requests"],
            "tokens_per_s": self.throughput_tokens_per_s(),
            "slo_attainment": (self._slo_hits / n) if n else None,
            "p50_latency_s": pct(0.50),
            "p99_latency_s": pct(0.99),
            "slot_occupancy": (self._occupancy_sum / steps) if steps else 0.0,
            "host": {**self._host, "step_s": self.stats["wall_s"],
                     "host_s": self.stats["wall_s"] - self._host["launch_s"]
                     - self._host["wait_s"]},
            "pipeline": dict(self._pipeline),
            "cache": {"bytes": self._cache_bytes, **self._cache},
        }


class StreamSimulator:
    """Camera streams enqueueing fixed-size frame requests at a frame rate.

    Works with either engine (both expose submit/drain/cfg)."""

    def __init__(self, engine, prompt_len: int = 32,
                 new_tokens: int = 8, vocab: Optional[int] = None,
                 seed: int = 0):
        self.engine = engine
        self.prompt_len = prompt_len
        self.new_tokens = new_tokens
        self.vocab = vocab or engine.cfg.vocab_size
        self.rng = np.random.default_rng(seed)
        self.frame_count = 0
        self._accum: dict[str, float] = {}

    def tick(self, streams_fps: dict[str, float], dt_s: float = 1.0) -> int:
        """Enqueue dt_s worth of frames for each stream at its fps.
        Fractional frames accumulate across ticks (a 0.25 fps camera emits
        one frame every 4 seconds). Each frame carries a 1/fps latency
        budget — the stream's frame period — which the deadline-aware
        engine uses for EDF ordering and SLO accounting."""
        n = 0
        for sid, fps in streams_fps.items():
            acc = self._accum.get(sid, 0.0) + fps * dt_s
            frames = int(acc)
            self._accum[sid] = acc - frames
            budget = (1.0 / fps) if fps > 0 else float("inf")
            for _ in range(frames):
                toks = self.rng.integers(
                    0, self.vocab, self.prompt_len).astype(np.int32)
                self.engine.submit(Request(
                    request_id=f"{sid}-f{self.frame_count}",
                    tokens=toks, max_new_tokens=self.new_tokens,
                    stream_id=sid, deadline_s=budget))
                self.frame_count += 1
                n += 1
        return n
