"""``repro.obs`` spans on the profiler trace's clock.

A span's ``t_start`` is ``time.time()``, the clock the profiler stamps its
start with, but the trace counts from its own start, which the reduction
does not keep.  Both records hold one mark per distance: the benchmark's
``bench.set_distance`` annotation in the trace and the front door's
``hd.set_distance`` span, opened a little after it.  The i-th of each, in
the order each record lists them, give one offset, the clocks' true
offset less that little delay.  The largest offset is the one nearest the
truth, and with it every span lands inside its mark (the median can place
a span a few microseconds before its mark).  Where the counts differ, or
the offsets spread by more than ``MAX_SPREAD_NS`` (a pairing that is off,
or a program without the span), there is no offset and the readers report
nothing.
"""
from __future__ import annotations

MARK = "bench.set_distance"
CALL = "hd.set_distance"
MAX_SPREAD_NS = 1e6


def offsets_ns(observed) -> list[float] | None:
    """Trace start of each mark less its call's ``t_start``, in ns."""
    t = observed.get("trace")
    if t is None:
        return None
    marks = [s for name, s, _ in t.spans if name == MARK]
    calls = [s["t_start"] for s in observed.get("spans", []) if s["name"] == CALL]
    if not marks or len(marks) != len(calls):
        return None
    return [m - 1e9 * c for m, c in zip(marks, calls)]


def offset_ns(observed) -> float | None:
    offs = offsets_ns(observed)
    if offs is None or max(offs) - min(offs) > MAX_SPREAD_NS:
        return None
    return max(offs)


def intervals(observed, name: str) -> list[tuple[float, float]] | None:
    """Every span called ``name`` as ``(start, end)`` on the trace's clock."""
    off = offset_ns(observed)
    if off is None:
        return None
    out = []
    for s in observed["spans"]:
        if s["name"] == name:
            start = 1e9 * s["t_start"] + off
            out.append((start, start + 1e9 * s["dur_s"]))
    return out
