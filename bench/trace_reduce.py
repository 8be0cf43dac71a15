"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

What a TPU trace holds (JAX 0.9, TPU v5e): one plane per chip named
``/device:TPU:<i>`` with a line ``XLA Modules`` (one event per program
run) and a line ``XLA Ops`` (the HLO ops, a ``while`` op enclosing the ops
of its body; a Pallas kernel is a ``custom-call`` op named after its
``pallas_call``); and a plane ``/host:CPU`` whose thread lines hold the
harness's ``TraceAnnotation`` spans.  All times are nanoseconds on one
clock.

* busy time of a chip: the union of its ``XLA Modules`` intervals inside
  the traced window (the harness's ``window`` span);
* kernel time: the union of the ``XLA Ops`` intervals whose op name, or
  whose ``tf_op`` / ``name_scope`` stat (where a ``jax.named_scope`` shows),
  contains the kernel's name;
* idle gaps: the holes between busy intervals inside the window, each
  labelled by the harness span that covers most of it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"
SPANS = ("enqueue", "readback", "generate", "flush", "poll")
WINDOW = "window"
SCOPE_STATS = ("tf_op", "name_scope")      # named-scope paths, not HLO text


def union(intervals):
    """Merge [start, end) pairs; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


@dataclasses.dataclass
class Chip:
    busy_ns: float
    modules: list            # disjoint busy intervals, clipped to the window
    ops: list                # (name, start, end, scope text)


@dataclasses.dataclass
class Trace:
    window: tuple            # (start, end) ns of the harness's window span
    chips: dict              # device index -> Chip
    spans: list              # (name, start, end) host spans of the harness

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the chips."""
        return sum(c.busy_ns for c in self.chips.values()) / 1e9 / len(self.chips)

    def kernel_s(self, name: str) -> tuple:
        """(seconds, events) of the ops that carry ``name`` inside the
        window, summed over the chips.  Only an op's own name counts, not
        the operands in its HLO text: a fusion that reads the kernel's
        output is not the kernel."""
        secs, count = 0.0, 0
        lo, hi = self.window
        for chip in self.chips.values():
            hit = [(s, e) for op, s, e, scope in chip.ops
                   if (name in op.split(" = ")[0] or name in scope)
                   and s < hi and e > lo]
            secs += total(clip(union(hit), *self.window)) / 1e9
            count += len(hit)
        return secs, count

    def top_ops(self, limit: int = 10) -> list:
        """Outermost device ops by summed time, [name, seconds]."""
        acc = collections.defaultdict(float)
        for chip in self.chips.values():
            reach = None
            for op, s, e, _ in sorted(chip.ops, key=lambda o: (o[1], -o[2])):
                if reach is not None and s < reach:
                    continue                      # nested in an earlier op
                reach = e
                acc[op.split(" = ")[0]] += (min(e, self.window[1])
                                            - max(s, self.window[0])) / 1e9
        rows = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
        return [[k, v] for k, v in rows]

    def idle_gaps(self, limit: int = 10) -> list:
        """The longest device-idle gaps inside the window, each named by the
        harness span that overlaps it most ("none" if no span does)."""
        gaps = []
        for chip in self.chips.values():
            edges = [self.window[0]] + [t for iv in chip.modules for t in iv] \
                + [self.window[1]]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((e - s, s, e))
        gaps.sort(reverse=True)
        rows = []
        for length, s, e in gaps[:limit]:
            best, label = 0.0, "none"
            for name, hs, he in self.spans:
                cover = min(e, he) - max(s, hs)
                if cover > best:
                    best, label = cover, name
            rows.append([label, length / 1e9])
        return rows


def _stat_text(event) -> str:
    parts = []
    for key, value in event.stats:
        if key in SCOPE_STATS:
            parts.append(str(value))
    return " ".join(parts)


def read(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    spans, window = [], None
    raw = {}
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
                    elif ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
        elif plane.name.startswith(DEVICE_PREFIX):
            idx = int(plane.name[len(DEVICE_PREFIX):])
            mods, ops = [], []
            for line in plane.lines:
                if line.name == MODULES:
                    mods = [(ev.start_ns, ev.end_ns) for ev in line.events]
                elif line.name == OPS:
                    ops = [(ev.name, ev.start_ns, ev.end_ns, _stat_text(ev))
                           for ev in line.events]
            raw[idx] = (mods, ops)
    if window is None:
        raise ValueError(f"no '{WINDOW}' span in {path}")
    if not raw:
        raise ValueError(f"no {DEVICE_PREFIX}* plane in {path}")
    chips = {}
    for idx, (mods, ops) in raw.items():
        busy = clip(union(mods), *window)
        chips[idx] = Chip(busy_ns=total(busy), modules=busy, ops=ops)
    return Trace(window=window, chips=chips, spans=spans)
