"""95th percentile, over every request finished in the window, of the
time from its enqueue to its first admission to a slot (host clock;
``RequestStats.queue_s``)."""

import numpy as np


def read(obs):
    waits = [getattr(st, "queue_s", None)
             for r in obs.segments for st in r.stats]
    if not waits or None in waits:
        return None
    return float(np.percentile(waits, 95))
