"""iters: mean iterations per solve of the window, from SolveResult.iters."""


def read(run):
    if not run.iters:
        return None
    return sum(run.iters) / len(run.iters)
