"""Sum of per-layer costs over a model's layer pattern, plus the output
head on ``tokens_out`` rows (the embedding lookup is a gather: its bytes
are counted, no operations)."""
from bench import costs


def total(m: dict, mode: str, arg, tokens_in: int, tokens_out: int,
          itemsize: int = 2) -> tuple[float, float]:
    pattern = m["block_pattern"]
    flops = nbytes = 0.0
    for i in range(m["num_layers"]):
        mixer, ffn = pattern[i % len(pattern)]
        for part in (f"mixer_{mixer}", None if ffn is None else f"ffn_{ffn}"):
            if part:
                f, b = getattr(costs.load(part), mode)(m, arg, itemsize)
                flops, nbytes = flops + f, nbytes + b
    D, V = m["d_model"], m["vocab_size"]
    flops += 2.0 * tokens_out * D * V
    nbytes += (D * V + tokens_in * D) * itemsize
    return flops, nbytes
