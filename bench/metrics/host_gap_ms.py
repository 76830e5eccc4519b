"""host_gap_ms: device idle time inside the program's own ``repro.solve``
host spans (``SolverSession.solve``: inputs, executable lookup, dispatch)
per solve, from a traced run's solves after the window
(``bench/scopes.py``; device trace on the host spans' clock)."""

from bench import scopes


def read(run):
    t = scopes.measure(run)
    if t is None or not t.solves:
        return None
    return 1e3 * t.host_gap_s / t.solves
