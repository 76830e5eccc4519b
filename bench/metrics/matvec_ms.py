"""matvec_ms: device self time in the program's ``repro.matvec`` scope
(``Ops.matvec``: the stencil apply, local or distributed, its halo
exchange left out) per loop iteration, from a traced run's solves after
the window (``bench/scopes.py``; device trace)."""

from bench import scopes


def read(run):
    t = scopes.measure(run)
    if t is None or not t.iters:
        return None
    return 1e3 * t.scope_busy_s.get("repro.matvec", 0.0) / t.iters
