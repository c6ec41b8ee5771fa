"""Forecasting + MPC tests (ISSUE 10), and the three time-unit bugfix
regressions that motivated them.

``hypothesis`` is optional (see DESIGN.md, Testing): when missing, seeded
random cases exercise the same invariants.

* ``PredictiveEWMAPolicy`` forecasts are a function of the demand *path*,
  not the control-loop period: the same linear ramp sampled at dt=1.0 and
  dt=0.5 yields the same trend state and the same forecasts (this test
  fails against the pre-fix per-tick units);
* ``LookaheadBid`` picks the same bids whether the simulator ticks hourly
  or every five minutes (the reclaim penalty is a dollar cost, not a
  per-tick rate);
* ``ScheduledPolicy`` resets its cadence phase and plan state per run: a
  reused policy's second run is bit-identical to a fresh policy's;
* ``AdaptiveManager.hold_until`` suppresses voluntary adoption only — and
  only until the deadline;
* ``SeasonalForecaster`` reproduces a pure-seasonal demand exactly, keeps
  residuals at zero on repeating days, and falls back to current rates on
  cold buckets;
* ``MPCPolicy`` never provisions below current demand, bounds its
  envelope by the feasibility caps, and collapses to the reactive policy
  (bit-identical ledger) when the forecaster is cold.
"""
import dataclasses
import math
import random

import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core import ResourceManager, Stream, fig6_catalog
from repro.core.adaptive import AdaptiveManager
from repro.core.markets import SPOT, MarketQuote
from repro.core.workload import PROGRAMS
from repro.sim import (FleetSimulator, LookaheadBid, MPCConfig, MPCPolicy,
                       PredictiveEWMAPolicy, ReactivePolicy, ScheduledPolicy,
                       SeasonalForecaster)
from repro.sim.demand import CameraSpec, DiurnalFleet
from repro.sim.scenarios import follow_the_sun, rush_hour


# ---------------------------------------------------------------- EWMA bugfix

def _ramp(t: float) -> list[Stream]:
    # one stream on a linear ramp: slope exactly 1 frame/s per hour
    return [Stream(stream_id="s0", program=PROGRAMS["ZF"], fps=2.0 + t)]


def test_ewma_forecast_is_dt_invariant():
    """The headline regression: the same demand path sampled at dt=1.0 and
    dt=0.5 must produce the same trend (frames/s per hour) and the same
    forecasts. Pre-fix, trend was frames/s per *tick* and the lead was in
    ticks, so the half-step schedule forecast roughly half the ramp."""
    hourly = PredictiveEWMAPolicy(ResourceManager(fig6_catalog()))
    halved = PredictiveEWMAPolicy(ResourceManager(fig6_catalog()))
    for t in (0.0, 1.0, 2.0):
        out_h = hourly.forecast(_ramp(t), 1.0)
    for t in (0.0, 0.5, 1.0, 1.5, 2.0):
        out_2 = halved.forecast(_ramp(t), 0.5)
    # same wall-clock endpoint, same trend units -> same smoothed slope
    # (approx, not exact: fractional decay goes through float pow)
    assert halved._trend["s0"] == pytest.approx(hourly._trend["s0"],
                                                rel=1e-12)
    assert out_2[0].fps == pytest.approx(out_h[0].fps, abs=1e-3)
    # and the trend really is the ramp slope in fps/hour, partially smoothed
    assert 0.0 < hourly._trend["s0"] <= 1.0


def test_ewma_dt_one_matches_legacy_form():
    """At the legacy 1-hour tick the decay/gain pair must be exactly
    ``1 - alpha`` / ``alpha`` — bit-identical goldens depend on it."""
    pol = PredictiveEWMAPolicy(ResourceManager(fig6_catalog()), alpha=0.3)
    pol.forecast(_ramp(0.0), 1.0)
    pol.forecast(_ramp(1.0), 1.0)
    # one update from zero state at trend 1.0: ewma == alpha exactly
    assert pol._trend["s0"] == 0.3


def test_ewma_lead_ticks_alias():
    pol = PredictiveEWMAPolicy(ResourceManager(fig6_catalog()), lead_ticks=3)
    assert pol.lead_h == 3.0 and pol.lead_ticks == 3.0
    pol.lead_ticks = 1.5
    assert pol.lead_h == 1.5
    # lead_h wins when both are passed
    pol2 = PredictiveEWMAPolicy(ResourceManager(fig6_catalog()),
                                lead_h=2.5, lead_ticks=4)
    assert pol2.lead_h == 2.5


def test_ewma_policy_resets_on_time_reversal():
    pol = PredictiveEWMAPolicy(ResourceManager(fig6_catalog()))
    for t in (0.0, 1.0, 2.0):
        pol.decide(t, _ramp(t))
    assert pol._trend["s0"] > 0
    pol.decide(0.0, _ramp(0.0))           # a new run begins
    assert pol._trend.get("s0", 0.0) == 0.0


# ----------------------------------------------------------- LookaheadBid fix

def _spot_quote(price: float, vol: float) -> MarketQuote:
    return MarketQuote(type_name="g2.2xlarge", location="us-east",
                       market=SPOT, price=price, ondemand_price=1.0,
                       volatility=vol)


@pytest.mark.parametrize("price,vol", [(0.2, 0.1), (0.3, 0.3), (0.6, 0.5),
                                       (0.9, 0.15)])
def test_lookahead_bid_is_dt_invariant(price, vol):
    """The reclaim penalty is the dollar cost of one reclaim and the
    expected-price model runs over a fixed horizon, so bid choices must not
    move with the control-loop period."""
    q = _spot_quote(price, vol)
    strat = LookaheadBid()
    assert strat.bid(q, (), 1.0) == strat.bid(q, (), 1.0 / 12.0)
    assert strat.bid(q, (), 1.0) == strat.bid(q, (), 4.0)


def test_lookahead_reclaim_cost_is_flat_dollars():
    strat = LookaheadBid(boot_delay_h=0.1, slo_weight=2.0)
    assert strat.reclaim_cost(_spot_quote(0.3, 0.2)) == \
        pytest.approx(2.0 * 1.0 * 0.1)


# ------------------------------------------------- ScheduledPolicy run reset

def test_scheduled_policy_two_runs_are_deterministic():
    sc = rush_hour(36)
    cat = sc.catalog()
    reused = ScheduledPolicy(ResourceManager(cat), every_h=6.0)
    led1 = FleetSimulator(sc.demand, reused, cat, sc.config).run()
    led2 = FleetSimulator(sc.demand, reused, cat, sc.config).run()
    fresh = ScheduledPolicy(ResourceManager(cat), every_h=6.0)
    led_f = FleetSimulator(sc.demand, fresh, cat, sc.config).run()
    assert led2.signature() == led_f.signature()
    assert led1.signature() == led_f.signature()


# ----------------------------------------------------------------- hold_until

def _streams(fps: float) -> list[Stream]:
    return [Stream(stream_id=f"s{i}", program=PROGRAMS["ZF"], fps=fps)
            for i in range(6)]


def test_hold_until_suppresses_voluntary_adoption_only():
    am = AdaptiveManager(ResourceManager(fig6_catalog()), strategy="FFD")
    am.step(0, _streams(8.0))
    expensive = am.current.hourly_cost
    am.hold_until = 5.0
    am.step(1, _streams(0.5))             # far cheaper candidate exists
    assert am.events[-1].action == "keep"
    assert am.current.hourly_cost == expensive
    # forced replans pass through the hold
    am.step(2, _streams(0.5), force=True)
    assert am.events[-1].action == "forced-replan"
    am.step(3, _streams(8.0))             # re-inflate, still holding
    am.hold_until = 5.0
    am.step(4, _streams(0.5))
    assert am.events[-1].action == "keep"
    am.step(5, _streams(0.5))             # deadline reached: adopt
    assert am.events[-1].action == "replan"
    assert am.current.hourly_cost < expensive


# ----------------------------------------------------------------- forecaster

def _tiny_fleet() -> DiurnalFleet:
    # one stream per (program, camera) class, so class means are exact
    return DiurnalFleet((CameraSpec("a", "nyc", "ZF", 0.5, 4.0),
                         CameraSpec("b", "london", "ZF", 0.3, 2.0),
                         CameraSpec("c", "nyc", "VGG16", 0.1, 1.5)))


def test_forecaster_reproduces_pure_seasonal_exactly():
    """Two identical days through a daily-period forecaster: every bucket
    holds two equal observations, so the fitted mean — and therefore the
    forecast — equals the demand exactly, and every residual is 0.0."""
    demand = _tiny_fleet()
    fc = SeasonalForecaster(period_h=24.0)
    fc.warmup(demand, 48.0)
    assert all(r == 0.0 for r in fc._resid.values())
    # forecasts queried on the observation grid (bucket granularity is the
    # model's resolution — off-grid hours forecast their bucket's value)
    for t in (0.0, 5.0, 13.0, 23.0):
        cols = demand.columns_at(t)
        pred, known = fc.forecast_fps(t, cols)
        assert known.all()
        np.testing.assert_array_equal(pred, np.asarray(cols.fps))
        assert fc.coverage(t, cols) == 1.0


def test_forecaster_residuals_stay_near_zero_on_repeats():
    demand = _tiny_fleet()
    fc = SeasonalForecaster(period_h=24.0)
    fc.warmup(demand, 24.0 * 5)           # five identical days
    scale = max(float(np.max(demand.columns_at(t).fps))
                for t in range(24)) or 1.0
    assert all(abs(r) <= 1e-12 * scale for r in fc._resid.values())


def test_forecaster_cold_start_falls_back_to_current():
    fc = SeasonalForecaster()
    streams = [Stream(stream_id="x", program=PROGRAMS["ZF"], fps=3.3)]
    pred, known = fc.forecast_fps(5.0, streams)
    assert not known.any()
    assert pred[0] == 3.3
    assert fc.coverage(5.0, streams) == 0.0


def test_forecaster_object_and_columnar_paths_agree():
    demand = _tiny_fleet()
    fc_cols = SeasonalForecaster(period_h=24.0)
    fc_objs = SeasonalForecaster(period_h=24.0)
    for t in range(24):
        fc_cols.observe(float(t), demand.columns_at(float(t)))
        fc_objs.observe(float(t), list(demand.streams_at(float(t))))
    for t in (2.0, 11.0, 19.0):
        cols = demand.columns_at(t)
        objs = list(demand.streams_at(t))
        pc, kc = fc_cols.forecast_fps(t, cols)
        po, ko = fc_objs.forecast_fps(t, objs)
        order = np.argsort([s.stream_id for s in objs])
        corder = np.argsort(list(cols.ids))
        np.testing.assert_allclose(np.asarray(pc)[corder], po[order],
                                   rtol=1e-12)
        assert kc.all() and ko.all()


def test_forecaster_live_scale_tracks_hotter_day():
    class Hub:
        def __init__(self):
            self.fns = []

        def subscribe(self, fn):
            self.fns.append(fn)

    class Point:
        def __init__(self, t, name, value):
            self.t, self.name, self.value = t, name, value

    fc = SeasonalForecaster(period_h=24.0)
    demand = _tiny_fleet()
    fc.warmup(demand, 24.0)
    hub = Hub()
    fc.attach_hub(hub)
    # day 1 through the hub primes the fleet curve (each bucket's first
    # observation has nothing to compare against, so the scale stays 1.0);
    # day 2 runs 1.5x hot and the live scale follows
    base = [float(np.asarray(demand.columns_at(float(t)).fps).sum())
            for t in range(24)]
    for t in range(7):
        for fn in hub.fns:
            fn(Point(float(t), "fleet.frames.demanded", base[t] * 3600.0))
    assert fc.live_scale() == 1.0
    for t in range(24, 31):
        for fn in hub.fns:
            fn(Point(float(t), "fleet.frames.demanded",
                     base[t % 24] * 1.5 * 3600.0))
    assert fc.live_scale() == pytest.approx(1.5)


# ------------------------------------------------------------------------ MPC

def test_mpc_envelope_never_below_current_demand():
    sc = follow_the_sun(24)
    fc = SeasonalForecaster()
    fc.warmup(sc.demand, 24.0)
    pol = MPCPolicy(ResourceManager(sc.catalog()), forecaster=fc)
    for t in (0.0, 6.0, 7.0, 12.0, 18.0, 23.0):
        cols = sc.demand.columns_at(t)
        cur = np.asarray(cols.fps)
        for lead in (0.0, 1.0, 2.0):
            env, n_pre = pol._envelope(t, cols, cur, lead)
            assert (env >= cur).all()
            # bounded by the feasibility caps (above current demand)
            caps = pol._caps(cols)
            assert (env <= np.maximum(caps, cur) + 1e-9).all()
            assert n_pre == int(np.count_nonzero(env > cur + 1e-9))
            if lead == 0.0:
                assert n_pre == 0 and (env == cur).all()


def test_mpc_cold_start_is_bit_identical_to_reactive():
    """With a cold forecaster the envelope degenerates to current demand;
    configured at the reactive policy's own hysteresis/cadence the whole
    run must be bit-identical to ``ReactivePolicy``."""
    sc = rush_hour(36)
    cat = sc.catalog()
    led_r = FleetSimulator(sc.demand, ReactivePolicy(ResourceManager(cat)),
                           cat, sc.config).run()
    pol = MPCPolicy(ResourceManager(cat),
                    config=MPCConfig(savings_threshold=0.10,
                                     cadence_candidates=(1.0,)))
    led_m = FleetSimulator(sc.demand, pol, cat, sc.config).run()
    assert led_m.signature() == led_r.signature()
    assert led_m.totals()["preboots"] == 0


def test_mpc_nonspot_exposes_no_bids():
    """Regression: a non-None ``bids`` attribute flips the cluster into
    market-aware reconciliation (no ``spot_fraction`` booking), silently
    repricing a pure on-demand policy's whole fleet."""
    pol = MPCPolicy(ResourceManager(fig6_catalog()))
    assert pol.bids is None
    spot = MPCPolicy(ResourceManager(fig6_catalog()), spot=True)
    assert spot.bids == {}


def test_mpc_warm_run_prebooks_and_resets_per_run():
    sc = follow_the_sun(24)
    cat = sc.catalog()
    fc = SeasonalForecaster()
    fc.warmup(sc.demand, 24.0)
    pol = MPCPolicy(ResourceManager(cat), forecaster=fc,
                    config=MPCConfig(slo_floor=0.999))
    led1 = FleetSimulator(sc.demand, pol, cat, sc.config).run()
    assert led1.totals()["preboots"] > 0
    # forecast error was scored against realized demand at least once
    assert led1.totals()["forecast_max_rel_error"] >= 0.0
    led2 = FleetSimulator(sc.demand, pol, cat, sc.config).run()
    assert led2.signature() == led1.signature()


# ------------------------------------------------ property-style invariants

def _random_fps_cases():
    rng = random.Random(7)
    return [[round(rng.uniform(0.1, 8.0), 3) for _ in range(5)]
            for _ in range(20)]


if HAVE_HYPOTHESIS:
    @given(st.lists(st.floats(min_value=0.1, max_value=8.0,
                              allow_nan=False), min_size=1, max_size=8))
    @settings(max_examples=50, deadline=None)
    def test_forecaster_constant_demand_is_forecast_verbatim(fps):
        _check_constant_demand(fps)
else:
    @pytest.mark.parametrize("fps", _random_fps_cases())
    def test_forecaster_constant_demand_is_forecast_verbatim(fps):
        _check_constant_demand(fps)


def _check_constant_demand(fps):
    """Constant per-class demand observed twice forecasts verbatim (two
    equal observations average exactly), for any rates."""
    streams = [Stream(stream_id=f"s{i}", program=PROGRAMS["ZF"], fps=f,
                      camera=f"cam{i}")
               for i, f in enumerate(fps)]
    fc = SeasonalForecaster(period_h=24.0)
    fc.observe(3.0, streams)
    fc.observe(27.0, streams)
    pred, known = fc.forecast_fps(51.0, streams)
    assert known.all()
    assert pred.tolist() == [s.fps for s in streams]


def test_class_index_cache_is_not_fooled_by_a_reused_address():
    """The columnar class cache keeps the arrays it was keyed on: a stable
    fleet (same ids list, same camera codes) whose new program codes land
    where the freed old codes were gets its own classes, not the old
    ones."""
    from repro.sim.demand import StreamColumns

    ids, cams = [f"s{i}" for i in range(4)], np.full(4, -1, np.int64)
    fc = SeasonalForecaster()
    for _ in range(20):
        codes = np.asarray([0, 0, 0, 0], np.int64)
        assert fc._class_index(StreamColumns(
            ids, np.ones(4), codes, ("a", "b"), cams, ()))[0] == [("a", "")]
        del codes
        codes = np.asarray([1, 1, 0, 1], np.int64)
        keys, inv = fc._class_index(StreamColumns(
            ids, np.ones(4), codes, ("a", "b"), cams, ()))
        assert keys == [("a", ""), ("b", "")]
        assert inv.tolist() == [1, 1, 0, 1]
