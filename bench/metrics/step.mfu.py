"""Model operations of every prefill and decode call in the traced window
(``bench/costs``: active slots only, causal halves, unpadded prompts) over
those calls' device time times the chip's bf16 peak, in percent. Device
trace."""
from bench import costs


def read(run):
    if not run.peak:
        return None
    flops = dev = 0.0
    for kind, name, arg in (("prefill", "prefill_into_slot_step", "S"),
                            ("decode", "decode_step", "positions")):
        step = costs.load(name)
        for entry, c in run.traced_calls(kind):
            if c.launches:
                flops += step.cost(run.model, entry[arg])[0]
                dev += c.device_s
    if dev <= 0:
        return None
    return 100.0 * flops / (dev * run.peak["bf16_flops_per_s"])
