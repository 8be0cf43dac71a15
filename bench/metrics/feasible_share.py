"""Share of the window's draws whose allocation meets the round deadline
(the program's ``feasible`` flag), in %.  A cell whose draws are all
infeasible times a degenerate round, which this shows."""


def read(run):
    return run.counters.get("feasible_share")
