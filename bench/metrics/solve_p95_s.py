"""solve_p95_s: the 95th percentile, by nearest rank, of the durations of
all the window's solves (host clock, each ending in block_until_ready)."""

import math


def read(run):
    d = sorted(run.durations)
    if not d:
        return None
    return d[math.ceil(0.95 * len(d)) - 1]
