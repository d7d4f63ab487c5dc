"""Gateway: median time a request waited in its queue (``t_dispatch -
t_submit`` of each request answered in the window), in ms."""
import numpy as np


def read(m):
    done = m.done_in_window()
    if not done.any():
        return None
    wait = m.rows["t_dispatch"][done] - m.rows["t_submit"][done]
    return 1e3 * float(np.median(wait))
