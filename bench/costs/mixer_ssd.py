"""One Mamba-2 SSD layer (``ssd``): input projection to (z, x, B, C, dt),
depthwise conv, the selective scan, the gated norm and the output
projection. ``prefill`` for one prompt of ``S`` tokens (the scan as
``ssd_scan`` counts it); ``decode`` for one token in each active slot: the
state update ``h = exp(dt A) h + dt B x`` (3 operations per state entry)
and the readout ``C h`` (2). Bytes: the layer's weights once; the f32
state and the conv window read and written per active slot (decode)."""
from bench.costs import ssd_scan


def _dims(m: dict):
    D, N, P = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    di = m["ssm_expand"] * D
    return D, di, di // P, P, N, di + 2 * N


def _weights(m: dict) -> int:
    D, di, H, P, N, conv_ch = _dims(m)
    return D * (2 * di + 2 * N + H) + di * D + m["ssm_conv"] * conv_ch


def prefill(m: dict, S: int, itemsize: int = 2) -> tuple[float, float]:
    D, di, H, P, N, conv_ch = _dims(m)
    f, _ = ssd_scan.cost(S, H, P, N, m["ssm_chunk"])
    flops = 2.0 * S * (D * (2 * di + 2 * N + H) + di * D) \
        + 2.0 * S * m["ssm_conv"] * conv_ch + f
    state = H * P * N * 4 + (m["ssm_conv"] - 1) * conv_ch * itemsize
    return flops, float(_weights(m) * itemsize + state)


def decode(m: dict, positions, itemsize: int = 2) -> tuple[float, float]:
    D, di, H, P, N, conv_ch = _dims(m)
    n = len(list(positions))
    per = 2.0 * (D * (2 * di + 2 * N + H) + di * D) \
        + 2.0 * m["ssm_conv"] * conv_ch + 5.0 * H * P * N
    state = 2 * (H * P * N * 4 + (m["ssm_conv"] - 1) * conv_ch * itemsize)
    return n * per, float(_weights(m) * itemsize + n * state)
