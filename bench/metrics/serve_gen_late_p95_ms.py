"""95th percentile of how late the load generator submitted a request
(submit time - due time), in ms."""


def read(run):
    return run.counters.get("gen_late_p95_ms")
