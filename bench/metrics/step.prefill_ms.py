"""Mean device time of one ``prefill_into_slot_step`` call (one admission)
in the traced window: the device modules launched inside the call's span.
Device trace."""


def read(run):
    calls = [c for _, c in run.traced_calls("prefill") if c.launches]
    return 1e3 * sum(c.device_s for c in calls) / len(calls) if calls else None
