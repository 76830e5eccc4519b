"""halo_ms: device self time in the program's ``repro.halo`` scope
(``DistributedOp``'s pad exchange: the halo planes' ``ppermute``s and the
padded operand's assembly) per loop iteration, averaged over the cell's
chips, from a traced run's solves after the window (``bench/scopes.py``;
device trace)."""

from bench import scopes


def read(run):
    t = scopes.measure(run)
    if t is None or not t.iters:
        return None
    return 1e3 * t.scope_busy_s.get("repro.halo", 0.0) / t.iters
