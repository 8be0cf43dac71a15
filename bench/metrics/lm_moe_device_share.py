"""Share of the FL round program's busy time spent in the language model's
MoE layers, in %: the union of the device intervals of the ops under the
``lm.moe`` named scope (``models.nemotron_h``: routing, the grouped expert
matmuls, the shared expert, forward and backward) over the busy time.  The
ops' scopes come from the compiled text of the round program (the driver's
``compiled_text``) through ``program_trace.metric_scopes``; a fusion takes
its root's scope, so the share is approximate.  None without a trace, or
where the program has no such scope."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    scopes = program_trace.metric_scopes("lm_moe_device_share", run.trace)
    return program_trace.scope_share(run.trace, scopes, "lm.moe")
