"""Share of the traced window in which the chip ran no program, in %.  It
reads every ``device_idle.<suffix>`` metric, each split by the end-to-end
metric it moves."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
