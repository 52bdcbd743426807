"""95th percentile of the latency of the requests due in the traced
stretch, timed as the window's median is: from when the schedule said a
request was due to when its answer is on the host."""

import numpy as np


def read(ctx):
    lat = ctx["counters"].get("stretch_latency_ms")
    return float(np.percentile(lat, 95.0)) if lat is not None and len(lat) else None
