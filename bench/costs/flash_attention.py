"""``flash_attention`` on one prompt: causal self-attention of ``S``
queries over the same ``S`` keys, ``H`` query heads and ``K`` key/value
heads of width ``hd``. Operations: the two matmuls (scores, weighted sum)
over the causal half, ``S (S + 1) / 2`` query-key pairs, 2 each per
multiply-add. Bytes: q, k, v read and the output written once."""


def cost(S: int, H: int, K: int, hd: int, itemsize: int = 2,
         batch: int = 1) -> tuple[float, float]:
    pairs = S * (S + 1) / 2
    flops = 4.0 * batch * H * hd * pairs
    nbytes = float(batch * S * hd * (2 * H + 2 * K) * itemsize)
    return flops, nbytes
