"""Continuous-batching engine tests: greedy-token equivalence with the
static engine, slot reuse within one drain, deadline (EDF) admission, the
prefill-into-slot model step, stats sanity, and the engine's own
instrumentation (host counters, request stamps, profiler spans, program
names)."""
import gc
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model as M
from repro.models.config import get_config
from repro.models.steps import (make_jitted_decode, make_jitted_prefill,
                                make_jitted_prefill_into_slot)
from repro.serving import (ContinuousBatchingEngine, Request, ServingEngine,
                           StreamSimulator)
from repro.serving.engine import MAX_IN_FLIGHT

# the checkout's root, for the benchmark's trace reader
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import trace as tr  # noqa: E402

CACHE_LEN = 48
PROMPT_LEN = 16


def _setup(arch="olmo-1b", seed=0):
    cfg = get_config(arch, reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    return cfg, params


def _mixed_requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32),
             3 + (i % 4)) for i in range(n)]


# batch-independent mixers only: capacity-limited MoE routing depends on
# batch composition under either engine (see engine.py docstring)
@pytest.mark.parametrize("arch", [
    "olmo-1b", "mamba2-2.7b",
    pytest.param("recurrentgemma-9b", marks=pytest.mark.slow),
])
def test_continuous_matches_static_greedy_tokens(arch):
    cfg, params = _setup(arch)
    reqs = _mixed_requests(cfg, 6)

    static = ServingEngine(cfg, params, max_batch=3, cache_len=CACHE_LEN)
    for i, (t, m) in enumerate(reqs):
        static.submit(Request(f"r{i}", t.copy(), max_new_tokens=m))
    sdone = {r.request_id: r.output for r in static.drain()}

    cont = ContinuousBatchingEngine(cfg, params, max_slots=3,
                                    cache_len=CACHE_LEN)
    for i, (t, m) in enumerate(reqs):
        cont.submit(Request(f"r{i}", t.copy(), max_new_tokens=m))
    cdone = {r.request_id: r.output for r in cont.drain()}

    assert set(sdone) == set(cdone)
    for k in sdone:
        np.testing.assert_array_equal(sdone[k], cdone[k])


def test_finished_slot_reused_within_drain():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(0)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    eng.submit(Request("short", toks(), max_new_tokens=2))
    eng.submit(Request("long", toks(), max_new_tokens=8))
    eng.submit(Request("queued", toks(), max_new_tokens=4))

    # admits short+long and dispatches the decode of short's last (2nd)
    # token, which frees its slot; that token is read one step later
    done1 = eng.step()
    assert done1 == []
    freed = eng._slot_req.index(None)
    # queued admitted into the freed slot mid-decode, and short retires
    done2 = eng.step()
    returned = time.monotonic()
    assert [r.request_id for r in done2] == ["short"]
    assert done2[0].finish_t <= returned
    assert eng._slot_req[freed] is not None
    assert eng._slot_req[freed].request_id == "queued"
    assert eng._slot_req[1 - freed].request_id == "long"

    done = done1 + done2 + eng.drain()
    assert sorted(r.request_id for r in done) == ["long", "queued", "short"]
    assert eng.stats["prefills"] == 3


def test_deadline_aware_admission_is_edf():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=1,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(1)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    eng.submit(Request("lazy", toks(), max_new_tokens=2, deadline_s=60.0))
    eng.submit(Request("urgent", toks(), max_new_tokens=2, deadline_s=0.01))
    done = eng.drain()
    # urgent was submitted later but has the earlier deadline -> served first
    assert [r.request_id for r in done] == ["urgent", "lazy"]


def test_prefill_into_slot_matches_batched_prefill():
    """Admitting requests one-by-one into a pooled cache produces the same
    logits and cache as prefilling them together as one batch."""
    cfg, params = _setup()
    opts = M.ModelOptions(remat=False)
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab_size, (2, PROMPT_LEN)).astype(np.int32)

    prefill = make_jitted_prefill(cfg, opts, CACHE_LEN)
    logits_b, cache_b = prefill(params, {"tokens": jnp.asarray(toks)})

    slot_prefill = make_jitted_prefill_into_slot(cfg, opts, CACHE_LEN)
    cache = M.init_cache(cfg, 2, CACHE_LEN, jnp.float32, opts)
    logits0, cache = slot_prefill(params, cache,
                                  {"tokens": jnp.asarray(toks[:1])}, 0)
    logits1, cache = slot_prefill(params, cache,
                                  {"tokens": jnp.asarray(toks[1:])}, 1)

    np.testing.assert_allclose(np.asarray(logits_b[0]), np.asarray(logits0),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(logits_b[1]), np.asarray(logits1),
                               atol=1e-5, rtol=1e-5)
    for got, want in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_b)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_stats_monotonic_and_report_sane():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    sim = StreamSimulator(eng, prompt_len=PROMPT_LEN, new_tokens=3)
    prev = dict(eng.stats)
    for _ in range(3):
        sim.tick({"fast": 2.0, "slow": 0.5}, dt_s=1.0)
        while eng.queue or eng.active_slots():
            eng.step()
            for k in ("requests", "tokens_generated", "decode_steps",
                      "prefills"):
                assert eng.stats[k] >= prev[k], f"{k} decreased"
            assert eng.stats["wall_s"] >= prev["wall_s"]
            prev = dict(eng.stats)

    rep = eng.report()
    assert rep["requests"] == eng.stats["requests"] > 0
    assert rep["tokens_per_s"] >= 0.0
    assert 0.0 <= rep["slo_attainment"] <= 1.0
    assert 0.0 <= rep["p50_latency_s"] <= rep["p99_latency_s"]
    assert 0.0 < rep["slot_occupancy"] <= 1.0


def test_submit_rejects_oversized_request():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=1, cache_len=16)
    toks = np.zeros(12, np.int32)
    with pytest.raises(ValueError):
        eng.submit(Request("big", toks, max_new_tokens=8))


def test_report_with_no_completions_never_raises():
    """Percentiles of an empty completion list are None, not an error."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rep = eng.report()
    assert rep["requests"] == 0
    assert rep["tokens_per_s"] == 0.0
    assert rep["p50_latency_s"] is None
    assert rep["p99_latency_s"] is None
    # no completions = no evidence: None, not a perfect 1.0 (a drift
    # detector reading 1.0 off an idle engine would mask real regressions)
    assert rep["slo_attainment"] is None
    assert rep["slot_occupancy"] == 0.0
    assert eng.measured_rates() == {}


def test_measured_rates_per_stream_export():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(4)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    for i in range(3):
        eng.submit(Request(f"r{i}", toks(), max_new_tokens=4,
                           stream_id=f"cam-{i % 2}"))
    eng.drain()
    rates = eng.measured_rates()
    assert set(rates) == {"cam-0", "cam-1"}
    assert all(r > 0 for r in rates.values())
    # per-stream tallies account for every generated token; rates are per
    # active window, and streams submitted together share the full run, so
    # each stream's tokens reconstruct from its own window span
    total = sum(rates[sid] * (w[1] - w[0])
                for sid, w in eng._stream_window.items())
    assert total == pytest.approx(eng.stats["tokens_generated"])
    eng.reset_stats()
    assert eng.measured_rates() == {}


def test_measured_rates_late_joiner_not_underestimated():
    """Regression: rates used to divide by *total* wall time, so a stream
    that joined late looked slower than it served — phantom drift. Rates
    are now over each stream's own active window."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(11)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    # early stream runs alone for a while
    eng.submit(Request("r0", toks(), max_new_tokens=12, stream_id="early"))
    eng.drain()
    wall_before_join = eng.stats["wall_s"]
    assert wall_before_join > 0
    # late joiner arrives after the early traffic is done
    eng.submit(Request("r1", toks(), max_new_tokens=12, stream_id="late"))
    eng.drain()
    rates = eng.measured_rates()
    # same work, same decode cost: the late joiner's rate must reflect its
    # own window, not be diluted by the time before it existed
    first, last = eng._stream_window["late"]
    assert first >= wall_before_join
    late_tokens = eng._stream_tokens["late"]
    stale_rate = late_tokens / eng.stats["wall_s"]   # the old, buggy math
    assert rates["late"] == pytest.approx(late_tokens / (last - first))
    assert rates["late"] > stale_rate


def test_windowed_rates_delta_export():
    """windowed_rates() reports tokens/s since the previous poll — the
    streaming export a drift detector samples — and drains to empty."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(12)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    eng.submit(Request("r0", toks(), max_new_tokens=6, stream_id="cam-0"))
    eng.drain()
    first = eng.windowed_rates()
    assert set(first) == {"cam-0"}
    assert first["cam-0"] > 0
    # no new tokens since the poll: empty, not a repeat of old traffic
    assert eng.windowed_rates() == {}
    eng.submit(Request("r1", toks(), max_new_tokens=6, stream_id="cam-1"))
    eng.drain()
    second = eng.windowed_rates()
    assert set(second) == {"cam-1"}


def test_windowed_rates_consecutive_polls_partition_exactly():
    """Two consecutive polls split the completion stream with no token
    counted twice and none dropped: rate x span per window recovers the
    per-stream token deltas, and the windows sum to the lifetime tally."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(21)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)

    eng.submit(Request("r0", toks(), max_new_tokens=6, stream_id="cam-0"))
    eng.drain()
    wall_0 = eng._rate_snapshot[0]
    first = eng.windowed_rates()
    wall_1, tokens_1 = eng._rate_snapshot

    eng.submit(Request("r1", toks(), max_new_tokens=4, stream_id="cam-0"))
    eng.submit(Request("r2", toks(), max_new_tokens=5, stream_id="cam-1"))
    eng.drain()
    second = eng.windowed_rates()
    wall_2, tokens_2 = eng._rate_snapshot

    span_1, span_2 = wall_1 - wall_0, wall_2 - wall_1
    # window 1: only cam-0 traffic, and rate x span is its exact tally
    assert set(first) == {"cam-0"}
    assert first["cam-0"] * span_1 == pytest.approx(tokens_1["cam-0"])
    # window 2 carries exactly the deltas since the first poll
    assert set(second) == {"cam-0", "cam-1"}
    assert second["cam-0"] * span_2 == pytest.approx(
        tokens_2["cam-0"] - tokens_1["cam-0"])
    assert second["cam-1"] * span_2 == pytest.approx(tokens_2["cam-1"])
    # partition exactness: the two windows reassemble the lifetime tally
    for sid in ("cam-0", "cam-1"):
        assert (first.get(sid, 0.0) * span_1 + second.get(sid, 0.0) * span_2
                == pytest.approx(tokens_2[sid]))


def test_windowed_rates_empty_window_is_empty_dict():
    """A poll window with no completions must return {} — silence is "no
    data" for the drift detector, never a fleet of zero-rate streams."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    # before any traffic at all (wall clock never advanced)
    assert eng.windowed_rates() == {}
    rng = np.random.default_rng(22)
    eng.submit(Request("r0", rng.integers(0, cfg.vocab_size, PROMPT_LEN)
                       .astype(np.int32), max_new_tokens=4,
                       stream_id="cam-0"))
    eng.drain()
    assert set(eng.windowed_rates()) == {"cam-0"}
    # idle window: {} (not {"cam-0": 0.0}) even though the stream is known
    assert eng.windowed_rates() == {}
    assert eng.windowed_rates() == {}


def test_windowed_rates_departing_stream_lands_in_final_window():
    """A stream retiring mid-window is attributed to the window covering
    its completion, then disappears from later windows entirely."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    rng = np.random.default_rng(23)
    toks = lambda: rng.integers(0, cfg.vocab_size, PROMPT_LEN).astype(np.int32)
    # "departs" retires after 2 tokens while "stays" keeps decoding past it
    eng.submit(Request("d0", toks(), max_new_tokens=2, stream_id="departs"))
    eng.submit(Request("s0", toks(), max_new_tokens=8, stream_id="stays"))
    eng.drain()
    window = eng.windowed_rates()
    # the departed stream's final tokens are in this window...
    assert set(window) == {"departs", "stays"}
    span = eng._rate_snapshot[0]
    assert window["departs"] * span == pytest.approx(
        eng._stream_tokens["departs"])
    # ...and it is absent (not zero) from every window after its departure
    eng.submit(Request("s1", toks(), max_new_tokens=3, stream_id="stays"))
    eng.drain()
    assert set(eng.windowed_rates()) == {"stays"}


class _CollectingEngine:
    """submit()-only stand-in so StreamSimulator runs without a model."""

    def __init__(self):
        self.requests = []

    def submit(self, req):
        self.requests.append(req)


def test_tick_fractional_fps_accumulates_exactly():
    eng = _CollectingEngine()
    sim = StreamSimulator(eng, prompt_len=4, new_tokens=2, vocab=100)
    for _ in range(4):
        sim.tick({"half": 0.5}, dt_s=1.0)
    assert len(eng.requests) == 2          # 0.5 fps * 4 s = 2 frames exactly
    for _ in range(8):
        sim.tick({"half": 0.5, "quarter": 0.25}, dt_s=1.0)
    by_stream = {}
    for r in eng.requests:
        by_stream[r.stream_id] = by_stream.get(r.stream_id, 0) + 1
    assert by_stream == {"half": 6, "quarter": 2}
    # the frame period is the deadline budget
    assert eng.requests[-1].deadline_s in (2.0, 4.0)


@pytest.mark.parametrize("arch", ["olmo-1b", "recurrentgemma-9b"])
def test_kernel_engine_serves_prompt_off_the_block_grid(arch):
    """With the Pallas kernels on (interpret mode here; compiled on a TPU
    backend), the engine admits a 200-token prompt: longer than one 128-row
    kernel block and not a multiple of it. Its tokens match the ``jnp``
    path's."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), 4)
            for n in (200, PROMPT_LEN)]
    outs = []
    for use_kernels in (True, False):
        eng = ContinuousBatchingEngine(
            cfg, params, max_slots=2,
            opts=M.ModelOptions(remat=False, use_kernels=use_kernels))
        for i, (t, m) in enumerate(reqs):
            eng.submit(Request(f"r{i}", t.copy(), max_new_tokens=m))
        outs.append({r.request_id: r.output for r in eng.drain()})
    assert set(outs[0]) == {"r0", "r1"}
    for k in outs[0]:
        np.testing.assert_array_equal(outs[0][k], outs[1][k])


def test_serve_reduced_end_to_end():
    """``repro.launch.serve.serve`` at the reduced config: every frame
    request of 4 streams x 2 fps x 3 s answered with its 8 tokens, measured
    rates for every stream, and the three fleet plans."""
    from repro.launch.serve import serve
    out = serve("olmo-1b", reduced=True)
    assert out["frames_served"] == 24
    assert out["tokens_per_frame"] == [8]
    assert out["serving_report"]["requests"] == 24
    assert out["tokens_per_s"] > 0
    assert sorted(out["measured_stream_tokens_per_s"]) == [
        f"cam-{i}" for i in range(4)]
    assert set(out["fleet_plans"]) == {"per-stream", "uniform-big", "packed"}
    assert all(p["hourly_cost"] > 0 for p in out["fleet_plans"].values())


# -- one-step-ahead dispatch ---------------------------------------------------

NEW_TOKENS = (1, 8, 3, 1, 5, 2, 7, 4, 6, 1)   # every length from 1 to 8


def _one_step_ahead_requests(cfg, seed):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}", rng.integers(0, cfg.vocab_size, PROMPT_LEN)
                    .astype(np.int32), max_new_tokens=m)
            for i, m in enumerate(NEW_TOKENS)]


@pytest.mark.parametrize("arch", ["olmo-1b", "mamba2-2.7b"])
def test_pipelined_outputs_match_requests_run_one_at_a_time(arch):
    """Admissions interleaved with decodes (two submissions per step into
    three slots), tokens read one step behind: every request's tokens
    equal those of the request run alone through the static engine."""
    cfg, params = _setup(arch)
    static = ServingEngine(cfg, params, max_batch=1, cache_len=CACHE_LEN)
    want = {}
    for r in _one_step_ahead_requests(cfg, 5):
        static.submit(r)
        (r,) = static.step()
        want[r.request_id] = r.output

    eng = ContinuousBatchingEngine(cfg, params, max_slots=3,
                                   cache_len=CACHE_LEN)
    todo, got = _one_step_ahead_requests(cfg, 5), {}
    while todo or eng.queue or eng.active_slots():
        for r in todo[:2]:
            eng.submit(r)
        del todo[:2]
        got.update((r.request_id, r.output) for r in eng.step())
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_idle_step_leaves_every_request_finished():
    """Waves of requests, one of only one-token requests (no decode at
    all): after any step that leaves the queue empty and no slot active,
    every submitted request has its output and its finish time."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    reqs = _one_step_ahead_requests(cfg, 6)
    waves = [reqs[:3], [r for r in reqs[3:] if r.max_new_tokens == 1],
             [r for r in reqs[3:] if r.max_new_tokens > 1]]
    submitted, idle_steps = [], 0
    for wave in waves:
        for r in wave:
            eng.submit(r)
        submitted += wave
        while eng.queue or eng.active_slots():
            eng.step()
            if not eng.queue and not eng.active_slots():
                idle_steps += 1
                for r in submitted:
                    assert r.output is not None, r.request_id
                    assert len(r.output) == r.max_new_tokens
                    assert r.first_token_t <= r.finish_t
    assert idle_steps == len(waves)
    assert not eng._inflight


def test_pipeline_counters():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    for _ in _serve_steps(eng, cfg, 6):
        pass
    p = eng.report()["pipeline"]
    assert p["readbacks"] == p["overlapped"] + p["flushes"]
    assert p["overlapped"] > 0
    assert p["flushes"] == 1          # one drain, one idle step
    eng.reset_stats()
    assert eng.report()["pipeline"] == {"readbacks": 0, "overlapped": 0,
                                        "flushes": 0}


def test_next_decode_dispatched_before_previous_is_read():
    """While the engine is busy with at most one admission a step, decode
    k's tokens reach the host only after decode k + 1 has been dispatched,
    and the idle step reads the last. A burst of admissions never leaves
    more than ``MAX_IN_FLIGHT`` programs unread, and every read made while
    busy leaves a later program to run."""
    cfg, params = _setup()
    slots = 4
    eng = ContinuousBatchingEngine(cfg, params, max_slots=slots,
                                   cache_len=CACHE_LEN)
    dispatched, read, most = [0], [0], [0]
    decode, prefill, read_back = eng._decode, eng._prefill_slot, eng._read_back

    def counting_decode(*args):
        dispatched[0] += 1
        most[0] = max(most[0], len(eng._inflight) + 1)
        return decode(*args)

    def counting_prefill(*args):
        most[0] = max(most[0], len(eng._inflight) + 1)
        return prefill(*args)

    def checked_read_back(keep, overlapped):
        # a decode's token vector has a row per slot, a prefill's one row
        entries = eng._inflight[:max(0, len(eng._inflight) - keep)]
        read[0] += sum(tokens.shape == (slots,) for tokens, _ in entries)
        if not overlapped:
            assert read[0] == dispatched[0]
        elif entries:
            assert keep >= 1
            if not burst:
                assert read[0] < dispatched[0], (read[0], dispatched[0])
        return read_back(keep, overlapped)

    eng._decode, eng._prefill_slot = counting_decode, counting_prefill
    eng._read_back = checked_read_back
    # one submission a step into a pool that stays busy, then a burst
    idle = lambda: not eng.queue and not eng.active_slots()
    burst, todo, idle_steps = False, _one_step_ahead_requests(cfg, 8), 0
    while todo or not idle():
        if todo:
            eng.submit(todo.pop(0))
        eng.step()
        idle_steps += idle()
    assert dispatched[0] > len(NEW_TOKENS) and read[0] == dispatched[0]
    burst = True      # four admissions at once: six programs unbounded
    for _ in _serve_steps(eng, cfg, 8):
        idle_steps += idle()
    assert read[0] == dispatched[0]
    assert most[0] == MAX_IN_FLIGHT
    p = eng.report()["pipeline"]
    assert p["overlapped"] > 0 and p["flushes"] == idle_steps


# -- the engine's own instrumentation ------------------------------------------

PHASES = ("launch_s", "wait_s")


def _serve_steps(eng, cfg, n, seed=0, max_new=None):
    """Submit ``n`` mixed requests and step until drained; yields after
    every step."""
    for i, (t, m) in enumerate(_mixed_requests(cfg, n, seed)):
        eng.submit(Request(f"r{i}", t, max_new_tokens=max_new or m))
    while eng.queue or eng.active_slots():
        eng.step()
        yield


def test_host_counters_add_up_and_only_grow():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    prev, steps = eng.report()["host"], 0
    for _ in _serve_steps(eng, cfg, 5):
        steps += 1
        h = eng.report()["host"]
        assert h["steps"] == steps
        assert h["host_s"] + h["launch_s"] + h["wait_s"] == \
            pytest.approx(h["step_s"], rel=1e-9, abs=1e-12)
        assert h["host_s"] >= 0
        for k in ("step_s", "gc_pauses", "gc_pause_s", "gc_pause_max_s",
                  *PHASES):
            assert h[k] >= prev[k], f"{k} decreased"
        assert h["launch_s"] > prev["launch_s"]   # every step decodes
        prev = h
    assert steps > 1


@pytest.mark.parametrize("served", [False, True])
def test_host_counters_zero_when_nothing_served(served):
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    if served:
        for _ in _serve_steps(eng, cfg, 3):
            pass
        gc.collect()
        assert eng.report()["host"]["gc_pauses"] > 0
        eng.reset_stats()
    h = eng.report()["host"]
    assert set(h) == {"steps", "step_s", "launch_s", "wait_s", "host_s",
                      "gc_pauses", "gc_pause_s", "gc_pause_max_s"}
    assert all(v == 0 for v in h.values()), h


@pytest.mark.parametrize("max_new", [1, 4])
def test_request_stamps_in_order(max_new):
    """A one-token request needs no decode; the stamps still run
    enqueue <= admission <= first token <= finish."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    reqs = [Request(f"r{i}", t, max_new_tokens=max_new)
            for i, (t, _) in enumerate(_mixed_requests(cfg, 3))]
    assert all(math.isnan(r.admit_t) and math.isnan(r.first_token_t)
               for r in reqs)
    for r in reqs:
        eng.submit(r)
    done = eng.drain()
    assert len(done) == 3
    for r in reqs:
        assert r.enqueue_t <= r.admit_t <= r.first_token_t <= r.finish_t
        assert len(r.output) == max_new


def test_forced_collection_is_counted():
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    serving = _serve_steps(eng, cfg, 3)
    next(serving)
    before = eng.report()["host"]["gc_pauses"]
    gc.collect()
    for _ in serving:
        pass
    h = eng.report()["host"]
    assert h["gc_pauses"] >= before + 1
    assert 0 < h["gc_pause_max_s"] <= h["gc_pause_s"]
    # one hook per process, however many engines
    ContinuousBatchingEngine(cfg, params, max_slots=1, cache_len=CACHE_LEN)
    assert sum(type(cb).__name__ == "_GcPauses" for cb in gc.callbacks) == 1


@pytest.mark.parametrize("program", ["prefill_into_slot_step", "decode_step",
                                     "prefill_step"])
def test_step_programs_lower_under_their_names(program):
    """The device trace names a program after its lowered module, so each
    step program lowers as ``jit_<step>``, not ``jit__unknown``."""
    cfg, params = _setup()
    opts = M.ModelOptions(remat=False)
    cache = M.init_cache(cfg, 2, CACHE_LEN, jnp.float32, opts)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    lowered = {
        "prefill_into_slot_step": lambda: make_jitted_prefill_into_slot(
            cfg, opts, CACHE_LEN).lower(params, cache,
                                        {"tokens": i32(1, PROMPT_LEN)}, i32()),
        "decode_step": lambda: make_jitted_decode(cfg, opts).lower(
            params, cache, {"token": i32(2), "pos": i32(2)}),
        "prefill_step": lambda: make_jitted_prefill(
            cfg, opts, CACHE_LEN).lower(params, {"tokens": i32(2, PROMPT_LEN)}),
    }[program]()
    assert lowered.as_text().startswith(f"module @jit_{program} ")


# parent span of each engine span, as the engine module's docstring lists it
PARENT = {"serving.schedule": "serving.step", "serving.admit": "serving.step",
          "serving.decode.launch": "serving.step",
          "serving.decode.wait": "serving.step",
          "serving.retire": "serving.step",
          "serving.prefill.launch": "serving.admit"}


def _host_spans(planes) -> list:
    return sorted((ev for name, lines in planes.items()
                   if name.startswith("/host") for evs in lines.values()
                   for ev in evs
                   if ev.name.startswith("serving.") or ev.name == "host.gc"),
                  key=lambda ev: (ev.start, -ev.end))


def _parent(spans, ev):
    """The innermost ``serving.*`` span that holds ``ev``."""
    best = None
    for p in spans:
        if p.start > ev.start:
            break
        if p is not ev and p.name.startswith("serving.") and p.end >= ev.end:
            best = p
    return best


def test_spans_under_the_profiler_match_the_counters(tmp_path):
    """Served under ``jax.profiler.trace`` on the CPU: one ``serving.step``
    per step and one ``serving.admit`` per admission with its request id,
    the spans nest as documented, each phase's summed spans agree with its
    counter, and a forced collection shows as ``host.gc``."""
    cfg, params = _setup()
    eng = ContinuousBatchingEngine(cfg, params, max_slots=2,
                                   cache_len=CACHE_LEN)
    for _ in _serve_steps(eng, cfg, 2, seed=7):      # compile outside
        pass
    eng.reset_stats()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for k, _ in enumerate(_serve_steps(eng, cfg, 5)):
            if k == 1:
                gc.collect()
    h = eng.report()["host"]
    spans = _host_spans(tr.load(tr.find_xplane(str(tmp_path))))
    by = {}
    for ev in spans:
        by.setdefault(ev.name, []).append(ev)

    assert len(by["serving.step"]) == h["steps"] > 0
    assert sorted(ev.stats["request_id"] for ev in by["serving.admit"]) == \
        [f"r{i}" for i in range(5)]
    assert sorted(ev.stats["request_id"] for ev in by["serving.retire"]) == \
        [f"r{i}" for i in range(5)]
    assert all(ev.stats["prompt_tokens"] == PROMPT_LEN
               for ev in by["serving.admit"])
    assert {ev.stats["generation"] for ev in by["host.gc"]} >= {2}
    for ev in spans:
        parent = _parent(spans, ev)
        if ev.name == "host.gc":
            continue
        assert (parent.name if parent else None) == PARENT.get(ev.name), \
            (ev.name, ev.stats)

    def total(*names):
        return sum(ev.end - ev.start for n in names for ev in by[n])

    for counter, names in (
            ("step_s", ("serving.step",)),
            ("launch_s", ("serving.prefill.launch", "serving.decode.launch")),
            ("wait_s", ("serving.decode.wait",))):
        assert total(*names) == pytest.approx(
            h[counter], rel=0.05, abs=1e-3), counter
