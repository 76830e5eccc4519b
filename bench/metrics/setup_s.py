"""setup_s: process start to the window's start, by the host clock, less
the TPU runtime's start (``jax.devices()``, made before the program is
imported): imports, the session and its compile (or cache load), the
right-hand sides made on the device, and one warm-up solve."""


def read(run):
    return run.setup_s
