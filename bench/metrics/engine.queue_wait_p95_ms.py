"""95th percentile of due time to admission into a slot, over the frames
admitted; the admission time is taken where the traced run wraps the
engine's admission. Program span."""
import math

import numpy as np


def read(run):
    waits = [r.admit_t - r.due_t for r in run.records
             if not math.isnan(r.admit_t)]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
