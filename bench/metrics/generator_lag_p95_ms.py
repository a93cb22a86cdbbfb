"""95th percentile of how late the load generator submitted a request
after it fell due (open loop; a starved generator shows here)."""

import numpy as np


def read(rec):
    lag = rec["generator_lag_s"]
    return float(np.percentile(lag, 95) * 1e3) if len(lag) else None
