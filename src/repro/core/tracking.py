"""Shared recompile accounting for every jitted engine entry point, and
the program's span vocabulary.

``TRACE_COUNTS`` counts actual traces (the Python body of a jitted function
only runs when XLA compiles a new specialization) — the proof object behind
the zero-mid-sweep-recompile tests and the benchmarks' ``recompiles``
fields.  It lives in its own module so both ``stackelberg`` (which re-exports
it — the historical import site) and ``sic`` can increment it without an
import cycle (``stackelberg`` imports ``sic``).

``SPANS`` names every host span the program records, and ``span`` opens
one: a ``jax.profiler.TraceAnnotation``, which records only while a
profiler session is on (well under a microsecond otherwise) and lands on
the profiler's host plane, on the device trace's clock.
"""
from __future__ import annotations

import collections

from jax.profiler import TraceAnnotation

TRACE_COUNTS: collections.Counter = collections.Counter()


def reset_trace_counts() -> None:
    """Zero every trace counter (the jit caches themselves are untouched).

    Test isolation: ``TRACE_COUNTS`` deltas asserted in one test must not
    depend on which other tests ran first — an autouse fixture calls this
    before each test, so every assertion starts from a clean counter and
    snapshots its own ``before`` value."""
    TRACE_COUNTS.clear()


# layer boundaries: the equilibrium tier's operand canonicalisation and its
# jitted dispatch; the allocation service's submit, batch packing, dispatch
# call and reap
SPANS = ("equilibrium.canon", "equilibrium.launch",
         "serve.submit", "serve.pack", "serve.launch", "serve.reap")


def span(name: str, **ids) -> TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``), its ``ids`` (such as
    ``rid=`` or ``batch=``) joining the spans of one request."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; add it to SPANS: {SPANS}")
    return TraceAnnotation(name, **ids)
