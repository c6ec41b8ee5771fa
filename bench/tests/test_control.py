"""The comparison that decides ``correct``, shown to fail, at the reduced
size the CPU can hold:

- the control (the reference in float8 put in the program's place) reads
  a widest gap over the cell's rehearsal limit on three seeds, where the
  program reads under it;
- a whole run with the timed path broken underneath comes out not correct,
  once for each fault a one-chip serving cell can have: a decode step that
  returns its cache unchanged, half of the batch left out, a token altered
  where it is produced. (No cell spans chips, so no exchange can be left
  out.)
"""
import time

import jax.numpy as jnp
import pytest

from bench import harness, modes

CELLS = ["olmo-1b.readout-steady", "mamba2-2.7b.readout-steady"]


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name):
    cell = harness.load_cell(name)
    limit = cell.load["rehearsal"]["limits"]["widest_gap"]
    for seed in (2**33 + 1, 2**33 + 2, 2**33 + 3):
        row = modes.calibrate_seed(cell, seed, 2.0, rehearse=True)
        assert row["tokens"] >= 100
        assert row["program_widest_gap"] <= limit, row
        assert row["control_widest_gap"] > limit, row


def _unchanged_state(eng):
    decode = eng._decode

    def call(params, cache, batch):
        logits, _ = decode(params, cache, batch)
        return logits, cache
    eng._decode = call


def _half_batch(eng):
    """Every other active slot (half, rounded up) left out of the step."""
    decode = eng._decode

    def call(params, cache, batch):
        logits, cache = decode(params, cache, batch)
        left_out = jnp.asarray(eng.active_slots()[::2], jnp.int32)
        return logits.at[left_out].set(0.0), cache
    eng._decode = call


def _altered_token(eng):
    prefill = eng._prefill_slot

    def call(params, cache, batch, slot):
        logits, cache = prefill(params, cache, batch, slot)
        return jnp.roll(logits, 1), cache
    eng._prefill_slot = call


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_token])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault):
    cell = harness.load_cell(name)
    ok = harness.run(cell, 11, 2.0, trace=False, rehearse=True,
                     t_start=time.monotonic(), peaks={})
    assert ok["correct"] is True
    bad = harness.run(cell, 11, 2.0, trace=False, rehearse=True,
                      t_start=time.monotonic(), peaks={}, fault=fault)
    assert bad["correct"] is False
    assert bad["checks"]["widest_gap"]["value"] > \
        bad["checks"]["widest_gap"]["limit"]
