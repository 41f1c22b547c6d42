"""99th percentile, over every window request that was matched, of the
time from when it was due to its ``matched`` reply (ms, client clock).
Requests matched only during the drain count, with their whole wait."""

import numpy as np


def read(run):
    ms = run.match_ms[~np.isnan(run.match_ms)]
    return float(np.percentile(ms, 99)) if len(ms) else None
