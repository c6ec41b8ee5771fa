"""One MLP (``mlp``), gated (SwiGLU) or not, for ``tokens`` tokens.
Bytes: the weights once."""


def _weights(m: dict) -> int:
    return (3 if m["gated"] else 2) * m["d_model"] * m["d_ff"]


def prefill(m: dict, S: int, itemsize: int = 2) -> tuple[float, float]:
    return 2.0 * S * _weights(m), float(_weights(m) * itemsize)


def decode(m: dict, positions, itemsize: int = 2) -> tuple[float, float]:
    return prefill(m, len(list(positions)), itemsize)
