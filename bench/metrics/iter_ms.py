"""iter_ms: device busy time inside the per-solve annotations, averaged
over the cell's chips, per loop iteration (device trace)."""


def read(run):
    t = run.trace
    if t is None or t.solve_busy_s <= 0 or not sum(run.iters):
        return None
    return 1e3 * t.solve_busy_s / sum(run.iters)
