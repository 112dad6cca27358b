"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reads.

- device busy time: the union of the intervals in which an operation ran
  on each device, clipped to the traced window and averaged over the
  devices used; the idle share is one minus busy over the window;
- device time per operation name (each operation's own time, less the
  operations nested in it), for the kernels' times and the
  ``breakdown``'s top operations;
- each idle gap on the device, charged to the innermost host span open at
  its midpoint (the ``repro.obs`` spans, which ``obs.enable(xla=True)``
  writes into the trace, and the benchmark's own ``bench.*`` spans).

On a TPU the operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  A trace recorded on the CPU has no device
plane; there the events that carry an ``hlo_op`` stat stand in for them,
which is what the reduction's test uses.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

SPAN_PREFIXES = ("bench.", "engine.", "cascade.", "index.", "serve.")
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no span)"


@dataclasses.dataclass
class TraceSummary:
    """Device operations and host spans of one traced window, in ns."""

    window: tuple[float, float]
    ops: dict[int, list[tuple[str, float, float]]]   # device -> (name, start, end)
    spans: list[tuple[str, float, float]]             # (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: int) -> list[tuple[float, float]]:
        lo, hi = self.window
        ivs = sorted((max(s, lo), min(e, hi)) for _, s, e in self.ops[device]
                     if e > lo and s < hi)
        merged: list[list[float]] = []
        for s, e in ivs:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices that ran anything."""
        if not self.ops:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(d)) for d in self.ops]
        return sum(per) / len(per) * 1e-9

    def self_times(self) -> dict[str, float]:
        """Device seconds by operation name, each operation's own time: an
        operation that runs inside another (the body of a ``while``) is
        not counted again in the one around it."""
        lo, hi = self.window
        total: dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            stack: list[list] = []  # [name, end, own time]
            for name, s, e in sorted(evs, key=lambda t: (t[1], -t[2])):
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
                while stack and stack[-1][1] <= s:
                    done = stack.pop()
                    total[done[0]] += done[2] * 1e-9
                if stack:  # nested: not the outer operation's own time
                    stack[-1][2] -= min(e, stack[-1][1]) - s
                stack.append([name, e, e - s])
            for done in stack:
                total[done[0]] += done[2] * 1e-9
        return dict(total)

    def op_seconds(self, match) -> float:
        """Device seconds of the operations whose name ``match`` accepts."""
        return sum(v for k, v in self.self_times().items() if match(k))

    def top_ops(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.self_times().items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> list[list]:
        """Idle seconds of the first device, by the innermost host span
        open at each gap's midpoint."""
        if not self.ops:
            return []
        lo, hi = self.window
        busy = self.busy_intervals(min(self.ops))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        spans = sorted(self.spans, key=lambda t: t[1])
        total: dict[str, float] = defaultdict(float)
        active: list[tuple[str, float, float]] = []
        nxt = 0
        for s, e in gaps:  # in time order: sweep the spans once
            mid = 0.5 * (s + e)
            while nxt < len(spans) and spans[nxt][1] <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [sp for sp in active if sp[2] > mid]
            inner = min(active, key=lambda sp: sp[2] - sp[1], default=(NO_SPAN,))
            total[inner[0]] += (e - s) * 1e-9
        return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_by_span()}


def op_name(text: str) -> str:
    """The operation's own name, ``%fusion.12``, from the HLO text the
    trace prints (``%fusion.12 = f32[...] fusion(...)``)."""
    return text.split(" = ", 1)[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` into a :class:`TraceSummary`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[int, list[tuple[str, float, float]]] = defaultdict(list)
    spans: list[tuple[str, float, float]] = []
    host_ops: list[tuple[str, float, float]] = []
    window = None
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit():
            dev = int(name[len("/device:TPU:"):])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev].extend((op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == WINDOW_SPAN:
                        window = iv[1:]
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append(iv)
                    elif e.duration_ns > 0 and any(k == "hlo_op" for k, _ in e.stats):
                        host_ops.append(iv)
    if not ops and host_ops:
        ops[0] = host_ops
    if window is None:
        every = [t for evs in ops.values() for _, s, e in evs for t in (s, e)]
        every += [t for _, s, e in spans for t in (s, e)]
        window = (min(every), max(every)) if every else (0.0, 0.0)
    return TraceSummary(window=window, ops=dict(ops), spans=spans)
