"""``ssd_scan`` on one prompt: the chunked SSD scan of ``S`` positions in
chunks of ``chunk``, ``H`` heads of width ``P``, state ``N``, ``groups``
B/C groups. Per chunk of length ``l``: the causal half of ``C B^T`` and of
its product with ``x`` (``l (l + 1) / 2`` pairs, times ``N`` resp. ``P``),
the carried state's contribution ``C h`` (not in the first chunk) and the
state update ``B^T x`` (not after the last chunk), 2 operations per
multiply-add. Bytes: x read and y written (``x_itemsize``), dt read as f32,
B and C read once per group (``bc_itemsize``)."""


def cost(S: int, H: int, P: int, N: int, chunk: int, groups: int = 1,
         x_itemsize: int = 2, bc_itemsize: int = 2,
         batch: int = 1) -> tuple[float, float]:
    lens = [min(chunk, S - c) for c in range(0, S, chunk)]
    flops = 0.0
    for j, l in enumerate(lens):
        pairs = l * (l + 1) / 2
        flops += 2 * pairs * N + 2 * pairs * P
        if j > 0:
            flops += 2 * l * N * P
        if j < len(lens) - 1:
            flops += 2 * l * N * P
    flops *= batch * H
    nbytes = batch * (2 * S * H * P * x_itemsize + S * H * 4
                      + 2 * S * groups * N * bc_itemsize)
    return flops, float(nbytes)
