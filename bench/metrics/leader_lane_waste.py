"""Share of the vmapped leader ``while_loop``'s lane-iterations that wait
for the slowest draw of their call, 1 - mean/max of the iterations, in %,
averaged over the calls of the window."""


def read(run):
    return run.counters.get("leader_lane_waste")
