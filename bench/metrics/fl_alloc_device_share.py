"""Share of the FL grid program's busy time spent in the SIC power engine
of the allocation game, in %: the union of the device intervals of the ops
under the ``sic_power`` named scope (``stackelberg._leader_iteration``,
reached from the round through ``fl_round._allocate_traced``) over the busy
time, summed over the chips.  The ops' scopes come from the compiled text
of the grid program (the driver's ``compiled_text``), compiled again after
the window (``program_trace.metric_scopes``); a fusion takes its root's
scope, so the share is approximate.  None without a trace, or where the
program has no such scope."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    scopes = program_trace.metric_scopes("fl_alloc_device_share", run.trace)
    return program_trace.scope_share(run.trace, scopes, "sic_power")
