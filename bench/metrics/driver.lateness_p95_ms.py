"""How late the open-loop driver sent frames: 95th percentile of submit
time minus due time. A starved driver shows here, not as a slow engine.
Host clock."""
import numpy as np


def read(run):
    late = [r.submit_t - r.due_t for r in run.records]
    return 1e3 * float(np.percentile(late, 95)) if late else None
