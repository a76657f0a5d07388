"""latency_p50_ms: median due-to-claim latency of every request due in
the window; an undelivered request counts as infinite."""

import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s(), 50) * 1e3)
