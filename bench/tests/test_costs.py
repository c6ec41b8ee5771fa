"""Operation and byte counts against hand counts on small shapes, and
against XLA's own count of the compiled step programs: the benchmark's
model operations must never exceed what the program executes, so no share
of a peak or a roofline can read high from a miscount."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import costs, harness
from bench.costs import decode_step, flash_attention, prefill_into_slot_step, ssd_scan

ROOT = Path(__file__).resolve().parents[2]
TINY = {"num_layers": 1, "d_model": 2, "vocab_size": 3,
        "block_pattern": [["attn", "mlp"]], "num_heads": 1, "num_kv_heads": 1,
        "head_dim": 2, "d_ff": 4, "gated": True}


def test_flash_attention_hand_count():
    # S=2 causal: pairs (0,0), (1,0), (1,1); one multiply-add each for the
    # scores and for the weighted sum, at hd=1: 3 + 3 MACs = 12 operations
    assert flash_attention.cost(2, 1, 1, 1) == (12.0, 16.0)
    # GQA: bytes count q and o per query head, k and v per kv head
    assert flash_attention.cost(4, 4, 1, 8, itemsize=2)[1] == 4 * 8 * 10 * 2


def test_ssd_scan_hand_count():
    # S=3 in chunks of 2: chunk 0 (l=2): 3 pairs x (N + P) x 2 = 12, state
    # update 2*2 = 4; chunk 1 (l=1): 1 pair x 2 x 2 = 4, carried state 2
    assert ssd_scan.cost(3, 1, 1, 1, 2) == (22.0, 36.0)


def test_step_hand_count():
    # prefill of 2 tokens: projections 2*2*16, attention 4*2*3, MLP 2*2*24,
    # last-position head 2*2*3
    assert prefill_into_slot_step.cost(TINY, 2)[0] == 64 + 24 + 96 + 12
    # decode of two slots at positions 0 and 3: projections 2*16 each,
    # attention 4*2*(p+1), MLP 2*24 each, head 2*2*3 each
    assert decode_step.cost(TINY, [0, 3])[0] == \
        2 * 32 + 8 * 1 + 8 * 4 + 2 * 48 + 2 * 12


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(1000.0, 5.0, peak) == 10.0
    assert costs.least_time(10.0, 50.0, peak) == 5.0


@pytest.mark.parametrize("config", ["olmo-1b", "mamba2-2.7b"])
def test_model_operations_do_not_exceed_the_compiled_programs(config):
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    sizes = conf["rehearsal"]
    from repro.models import model as M
    from repro.models.steps import make_jitted_decode, make_jitted_prefill_into_slot
    # one layer: XLA counts a loop's body once, and a scan over one layer
    # is no loop
    model = {**sizes["model"], "num_layers": 1}
    cfg = harness.program_config(conf["arch"], model, rehearse=True)
    opts = M.ModelOptions(remat=False)
    slots, clen, S = 4, sizes["cache_len"], 272
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0),
                                                  jnp.bfloat16))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, slots, clen, jnp.bfloat16,
                                                opts))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    pre = make_jitted_prefill_into_slot(cfg, opts, clen).lower(
        params, cache, {"tokens": i32(1, S)}, i32()).compile()
    dec = make_jitted_decode(cfg, opts).lower(
        params, cache, {"token": i32(slots), "pos": i32(slots)}).compile()
    xla = lambda c: c.cost_analysis()["flops"]
    ours_pre = prefill_into_slot_step.cost(model, S)[0]
    ours_dec = decode_step.cost(model, [clen - 1] * slots)[0]
    assert 0.5 * xla(pre) < ours_pre <= xla(pre)
    assert 0.5 * xla(dec) < ours_dec <= xla(dec)


def test_unknown_device_is_refused(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v0 imaginary"
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    with pytest.raises(harness.Refused, match="not in bench/peaks.json"):
        harness.device_info(False, 1, peaks)
