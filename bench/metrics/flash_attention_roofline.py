"""``flash_attention``'s share of its roofline in the traced window, in
percent: the least time of the causal work each admitted prompt needs, at
its unpadded length, in every attention layer (``bench/costs``), over the
kernel's summed device time in those calls. Silent where a call's kernel
launches do not match its attention layers. Device trace."""
from bench import costs

KERNEL = "flash_attention"


def read(run):
    if not run.peak:
        return None
    m = run.model
    layers = sum(1 for i in range(m["num_layers"])
                 if m["block_pattern"][i % len(m["block_pattern"])][0] == "attn")
    if not layers:
        return None
    least = spent = 0.0
    for entry, c in run.traced_calls("prefill"):
        n = c.kernel_n.get(KERNEL, 0)
        if not n:
            continue
        if n != layers:
            return None
        f, b = costs.load(KERNEL).cost(entry["S"], m["num_heads"],
                                       m["num_kv_heads"], m["head_dim"])
        least += n * costs.least_time(f, b, run.peak)
        spent += c.kernel_s[KERNEL]
    return 100.0 * least / spent if spent > 0 else None
