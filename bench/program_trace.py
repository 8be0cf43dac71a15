"""What the program records about itself, reduced to per-layer numbers:
its host spans, the device time of its named scopes, and the allocation
service's request stages.

* program spans: the host events named in ``repro.core.tracking.SPANS``
  (``equilibrium.canon``, ``serve.pack``, ...), on the profiler's host
  plane and so on the device trace's clock;
* idle gaps labelled ``<harness span>/<program span>`` where a program
  span overlaps the gap (the one that overlaps most), else as
  ``trace_reduce.Trace.idle_gaps`` labels them;
* device time by scope: the ops whose HLO ``op_name`` holds a
  ``jax.named_scope`` such as ``sic_power``.  A TPU trace's op events carry
  only offsets and durations, so the op -> scope map comes from the
  compiled text of the program that ran, where each instruction names its
  scope path; an instruction without one (a fusion) takes its fused
  root's.  A fusion can merge ops of several scopes, so the share is
  approximate;
* the service's stage numbers, from ``AllocResult.stages``.

A checkout whose program records none of these (no ``SPANS``, no
``stages``, no scope) reads None, not zero.
"""
from __future__ import annotations

import glob
import json
import os
import re
from pathlib import Path

import numpy as np

from bench.trace_reduce import HOST_PLANE, clip, total, union

ROOT = Path(__file__).resolve().parents[1]
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def span_names() -> tuple:
    """The program's span vocabulary; empty where the program has none."""
    try:
        from repro.core.tracking import SPANS
    except ImportError:
        return ()
    return SPANS


def newest_trace(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` under it."""
    if not os.path.isdir(path):
        return path
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def program_spans(path: str) -> list:
    """(name, start_ns, end_ns, args) of every program span in the trace,
    in start order; ``args`` holds the span's ids (``rid``, ``batch``)."""
    from jax.profiler import ProfileData
    names = set(span_names())
    spans = []
    for plane in ProfileData.from_file(newest_trace(path)).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.append((ev.name, ev.start_ns, ev.end_ns,
                                  dict(ev.stats)))
    return sorted(spans, key=lambda s: s[1])


def span_means_ms(spans: list, window: tuple) -> dict:
    """Mean duration in ms and count of each span name, over the spans
    that start inside the window."""
    acc = {}
    for name, s, e, _ in spans:
        if window[0] <= s < window[1]:
            n, t = acc.get(name, (0, 0.0))
            acc[name] = (n + 1, t + (e - s))
    return {name: {"n": n, "mean_ms": t / n / 1e6}
            for name, (n, t) in sorted(acc.items())}


def _best(spans, s, e):
    best, label = 0.0, None
    for name, hs, he, *_ in spans:
        cover = min(e, he) - max(s, hs)
        if cover > best:
            best, label = cover, name
    return label


def label_gaps(trace, spans: list, limit: int = 10) -> list:
    """``trace.idle_gaps(limit)`` with each gap that a program span
    overlaps labelled ``<harness span>/<program span>``."""
    gaps = []
    for chip in trace.chips.values():
        edges = [trace.window[0]] + [t for iv in chip.modules for t in iv] \
            + [trace.window[1]]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    rows = []
    for length, s, e in gaps[:limit]:
        label = _best(trace.spans, s, e) or "none"
        inner = _best(spans, s, e)
        rows.append([f"{label}/{inner}" if inner else label, length / 1e9])
    return rows


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name`` scope path, from the text of a
    compiled module.  An instruction with no ``op_name`` that calls a
    computation (a fusion) takes the scope of that computation's root."""
    scope, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        scope[name] = op.group(1) if op else ""
        callee = _CALLS.search(line)
        if callee:
            calls[name] = callee.group(1)
        if line.lstrip().startswith("ROOT") and comp is not None:
            roots[comp] = name
    for name in scope:
        seen = set()
        at = name
        while not scope[name] and at in calls and at not in seen:
            seen.add(at)
            at = roots.get(calls[at], "")
            scope[name] = scope.get(at, "")
    return scope


def op_key(event_name: str) -> str:
    """A device trace op's instruction name: ``%fusion.26 = f32[...] ...``
    -> ``fusion.26``."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def scope_share(trace, scopes: dict, scope: str) -> float | None:
    """Share of the busy time, in %, in the union of the device ops whose
    scope path holds ``scope``; None where no instruction of ``scopes``
    has that scope (a program without it)."""
    if not any(scope in path for path in scopes.values()):
        return None
    busy = sum(c.busy_ns for c in trace.chips.values())
    if busy <= 0:
        return None
    held = 0.0
    for chip in trace.chips.values():
        hit = [(s, e) for op, s, e, _ in chip.ops
               if scope in scopes.get(op_key(op), "")]
        held += total(clip(union(hit), *trace.window))
    return 100.0 * held / busy


def metric_scopes(metric: str, trace, root: Path = ROOT) -> dict:
    """The op -> scope map of the program that ran, among the cells that
    ``metric`` lists in ``BENCHMARK.json`` whose driver gives the compiled
    text of its program (``compiled_text(config, traffic)``): the one whose
    instruction names cover most of the traced ops."""
    from bench.run import load_module, resolve
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    traced = {op_key(op) for c in trace.chips.values() for op, *_ in c.ops}
    best, cover = {}, -1
    for name in entry.get("workloads", []):
        spec = resolve(name, root)
        driver = load_module(spec["driver"],
                             f"bench_scopes_{spec['traffic']['driver']}")
        if not hasattr(driver, "compiled_text"):
            continue
        scopes = scope_map(driver.compiled_text(spec["config"],
                                                spec["traffic"]))
        hits = len(traced & scopes.keys())
        if hits > cover:
            best, cover = scopes, hits
    return best


def _nearest_rank(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q,
                               method="higher"))


def serve_stages(results) -> dict | None:
    """The service's stage numbers over the answered requests (each an
    ``AllocResult`` or None), in ms: p95 (nearest rank) of ``queue_s`` and
    of ``inflight_s + ready_wait_s`` per request, mean ``pack_s`` and
    ``readback_s`` per dispatch.  None where no row carries stages."""
    rows = [r.stages for r in results
            if r is not None and getattr(r, "stages", None)]
    if not rows:
        return None
    batches = {st["batch"]: st for st in rows}.values()
    return {
        "serve_queue_p95_ms": 1e3 * _nearest_rank(
            [st["queue_s"] for st in rows], 95),
        "serve_inflight_p95_ms": 1e3 * _nearest_rank(
            [st["inflight_s"] + st["ready_wait_s"] for st in rows], 95),
        "serve_pack_ms": 1e3 * float(np.mean([b["pack_s"] for b in batches])),
        "serve_readback_ms": 1e3 * float(np.mean(
            [b["readback_s"] for b in batches]))}
