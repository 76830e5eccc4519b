"""solve_s: the window's time, from the first solve's start to the last
completed solve's end, divided by the completed solves (host clock)."""


def read(run):
    if not run.durations:
        return None
    return run.window_s / len(run.durations)
