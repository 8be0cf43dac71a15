"""Share of the dispatched batch slots that carried a real request, in %:
1 - padded_slots / (dispatches x batch_width), from the service's own
counters."""


def read(run):
    c = run.counters
    if not c.get("dispatches"):
        return None
    return 100.0 * (1.0 - c["padded_slots"] / (c["dispatches"] * c["batch_width"]))
