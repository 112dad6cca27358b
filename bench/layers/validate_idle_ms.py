"""Device idle time per distance inside ``hd.validate``, the spans placed
on the trace's clock by ``_clock``."""
import bisect

from _clock import intervals


def idle_ns(busy: list[tuple[float, float]], start: float, end: float) -> float:
    """Length of ``[start, end]`` less what the sorted, disjoint ``busy``
    intervals cover of it."""
    covered = 0.0
    i = max(bisect.bisect_right(busy, (start,)) - 1, 0)
    while i < len(busy) and busy[i][0] < end:
        covered += max(0.0, min(end, busy[i][1]) - max(start, busy[i][0]))
        i += 1
    return (end - start) - covered


def read(observed):
    t = observed.get("trace")
    ivs = intervals(observed, "hd.validate")
    if t is None or not t.ops or not ivs:
        return None
    busy = t.busy_intervals(min(t.ops))
    return 1e-6 * sum(idle_ns(busy, s, e) for s, e in ivs) / len(ivs)
