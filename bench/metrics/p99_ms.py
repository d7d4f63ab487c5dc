"""Requests as clients see them: 99th percentile, in ms, of submit ->
answer over every request answered in the window, exact per request. In a
closed loop at saturation the tail follows the stalls (tuner work,
compiles, loads), so it is read here and not judged end to end."""
import numpy as np


def read(m):
    done = m.done_in_window()
    if not done.any():
        return None
    lat = m.rows["t_done"][done] - m.rows["t_submit"][done]
    return 1e3 * float(np.percentile(lat, 99))
