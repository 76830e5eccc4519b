"""update_ms: device self time in the program's ``repro.step`` scope
outside the scopes nested in it (matvec, halo, reduce, precond): the
method's vector updates, per loop iteration, from a traced run's solves
after the window (``bench/scopes.py``; device trace)."""

from bench import scopes


def read(run):
    t = scopes.measure(run)
    if t is None or not t.iters:
        return None
    return 1e3 * t.scope_busy_s.get("repro.step", 0.0) / t.iters
