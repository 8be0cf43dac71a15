"""Share of the chip's busy time spent in the SIC power engine, in %: the
union of the device intervals of the ops under the ``sic_power`` named
scope (``stackelberg._leader_iteration``) over the busy time.  The ops'
scopes come from the compiled text of the program the cell ran, compiled
again after the window (``program_trace.metric_scopes``); a fusion takes
its root's scope, so the share is approximate.  None without a trace, or
where the program has no such scope."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    scopes = program_trace.metric_scopes("sic_power_device_share", run.trace)
    return program_trace.scope_share(run.trace, scopes, "sic_power")
