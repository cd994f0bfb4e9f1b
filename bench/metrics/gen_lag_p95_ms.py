"""How late the load generator fed its samples: the 95th percentile of
(time fed - time due) over the samples fed in the traced stretch."""
import numpy as np


def read(ctx):
    lag = np.asarray(ctx.gen_lag_s)
    return float(np.percentile(lag, 95) * 1e3) if lag.size else None
