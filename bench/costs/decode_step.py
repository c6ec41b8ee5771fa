"""``decode_step``: one token for each active slot, at the slot's
position, through every layer, and its logits. Slots that ride along
empty do no useful work and are not counted."""
from bench.costs import _stack


def cost(m: dict, positions, itemsize: int = 2) -> tuple[float, float]:
    positions = list(positions)
    n = len(positions)
    return _stack.total(m, "decode", positions, n, n, itemsize)
