"""``prefill_into_slot_step``: one prompt of ``S`` tokens through every
layer, the logits of its last position, and its cache written into one
slot."""
from bench.costs import _stack


def cost(m: dict, S: int, itemsize: int = 2) -> tuple[float, float]:
    return _stack.total(m, "prefill", S, S, 1, itemsize)
