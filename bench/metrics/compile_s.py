"""compile_s: the session's compile seconds in this run, from
SolverSession.cache_stats() (a load from the persistent cache on a warm
checkout)."""


def read(run):
    return run.compile_s
