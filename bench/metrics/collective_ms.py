"""collective_ms: the time inside the per-solve annotations in which a
collective operation (halo ``collective-permute``, dot ``all-reduce``) ran
on the device, as a union of those operations, averaged over the cell's
chips, per loop iteration (device trace; the window summary's
``collective_s``).  On a TPU the halo permutes are asynchronous: what is
counted is their start and done operations, and a transfer that other
work hides is not."""


def read(run):
    t = run.trace
    if t is None or t.n_devices == 0 or not sum(run.iters):
        return None
    return 1e3 * t.collective_s / sum(run.iters)
