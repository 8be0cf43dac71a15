"""Share of the FL round program's busy time spent in the language model's
Mamba2 layers, in %: the union of the device intervals of the ops under
the ``lm.mamba`` named scope (``models.nemotron_h``: projections, the
conv, the SSD scan, the gated norm, forward and backward) over the busy
time, scopes as ``lm_moe_device_share`` reads them.  None without a trace,
or where the program has no such scope."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    scopes = program_trace.metric_scopes("lm_mamba_device_share", run.trace)
    return program_trace.scope_share(run.trace, scopes, "lm.mamba")
