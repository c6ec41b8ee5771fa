"""Operations and bytes of one call, computed from its shapes.

One file per kernel (``flash_attention``, ``ssd_scan``), per layer kind
(``mixer_<mixer>``, ``ffn_<ffn>``) and per step program
(``prefill_into_slot_step``, ``decode_step``), each returning
``(flops, bytes)``. The counts are of the work the result needs: causal
halves, unpadded lengths, active slots only, each weight read once. So a
share of a roofline or of a peak computed from them can only read low.
"""
from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"bench.costs.{name}")


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
