"""95th percentile, over every request finished in the window, of the
time from its enqueue to its last token (host clock)."""

import numpy as np


def read(obs):
    lat = [st.latency_s for r in obs.segments for st in r.stats]
    return float(np.percentile(lat, 95)) if lat else None
