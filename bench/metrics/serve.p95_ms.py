"""95th percentile latency of the window's requests, from when each was
due (as ``p95_ms``), read per layer where the end-to-end tail was found
two-moded: the scheduler's job cap decides whether the last requests of
a full batch wait one batch more."""

import numpy as np


def read(run):
    lat = run.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 95)) * 1e3
