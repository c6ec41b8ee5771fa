"""Median, over every frame due in the window, of due time to last token.
Host clock."""
import numpy as np


def read(run):
    lat = run.latencies()
    return 1e3 * float(np.percentile(lat, 50)) if len(lat) else None
