"""Mean Alg.-2 leader iterations per draw (``Allocation.iterations``) over
the calls of the window."""


def read(run):
    return run.counters.get("leader_iters_mean")
