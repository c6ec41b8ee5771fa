"""95th percentile, over the frames admitted, of the engine's own stamps
``Request.admit_t - Request.enqueue_t``: the wait in the engine's queue,
from submission to the start of admission, without the driver's lateness.
Silent where the program does not stamp admissions. Program span."""
import math

import numpy as np


def read(run):
    waits = [r.request.admit_t - r.request.enqueue_t for r in run.records
             if not math.isnan(getattr(r.request, "admit_t", math.nan))]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
