"""hbm_roofline: the least time the chip's HBM needs for the method's
bytes (the configuration's bytes per point per iteration, times the points
on one chip, times the iterations), over the device busy time inside the
per-solve annotations (device trace), in percent.  The loop is bound by
memory bandwidth: CG does about 2 flops per byte."""


def read(run):
    t = run.trace
    if t is None or t.solve_busy_s <= 0 or run.peaks is None:
        return None
    nbytes = (run.config["bytes_per_point_per_iter"] * run.points_per_chip
              * sum(run.iters))
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t.solve_busy_s
