"""Load generator: 99th percentile, in ms, of the time from a client's
answer to its next submit, over submits in the window. A high lag means
the generator, not the server, set the pace."""
import numpy as np


def read(m):
    t = m.rows["t_submit"]
    lag = m.rows["lag"][(t >= m.t0) & (t < m.t1)]
    lag = lag[~np.isnan(lag)]
    if not len(lag):
        return None
    return 1e3 * float(np.percentile(lag, 99))
