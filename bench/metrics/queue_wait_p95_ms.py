"""95th percentile of the wait from when a request fell due to the start
of the flush that served it (the harness's spans around
``SchedulerService``: due time, and the timed ``schedule_many`` seam)."""

import numpy as np


def read(rec):
    w = rec["queue_wait_s"]
    return float(np.percentile(w, 95) * 1e3) if len(w) else None
