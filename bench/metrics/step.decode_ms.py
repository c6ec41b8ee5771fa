"""Mean device time of one ``decode_step`` call in the traced window: the
device modules launched inside the call's span. Device trace."""


def read(run):
    calls = [c for _, c in run.traced_calls("decode") if c.launches]
    return 1e3 * sum(c.device_s for c in calls) / len(calls) if calls else None
