"""One attention layer (``attn``): q/k/v/o projections and causal
attention. ``prefill`` for one prompt of ``S`` tokens; ``decode`` for one
token in each of the active slots, whose positions are ``positions``
(attending over ``position + 1`` cached keys). Bytes: the layer's weights
once, the prompt's k/v written (prefill), each active slot's cache read
and one k/v row written (decode)."""
from bench.costs import flash_attention


def _proj(m: dict) -> int:
    D, H, K, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return D * H * hd * 2 + 2 * D * K * hd


def prefill(m: dict, S: int, itemsize: int = 2) -> tuple[float, float]:
    f, b = flash_attention.cost(S, m["num_heads"], m["num_kv_heads"],
                                m["head_dim"], itemsize)
    kv = 2 * S * m["num_kv_heads"] * m["head_dim"] * itemsize
    return 2.0 * S * _proj(m) + f, float(_proj(m) * itemsize + kv)


def decode(m: dict, positions, itemsize: int = 2) -> tuple[float, float]:
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = sum(2.0 * _proj(m) + 4.0 * H * hd * (p + 1) for p in positions)
    row = 2 * K * hd * itemsize
    nbytes = _proj(m) * itemsize + sum(row * (p + 2) for p in positions)
    return flops, float(nbytes)
