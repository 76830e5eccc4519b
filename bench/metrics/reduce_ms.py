"""reduce_ms: device self time in the program's ``repro.reduce`` scope
(``Ops.dot``/``dotn``, ``sum_partials``: the dot products and their psum)
per loop iteration, from a traced run's solves after the window
(``bench/scopes.py``; device trace)."""

from bench import scopes


def read(run):
    t = scopes.measure(run)
    if t is None or not t.iters:
        return None
    return 1e3 * t.scope_busy_s.get("repro.reduce", 0.0) / t.iters
