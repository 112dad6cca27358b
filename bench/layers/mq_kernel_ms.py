"""Device time of the query-axis bucket kernel per flush."""
from _spans import named
from _work import is_mq


def read(observed):
    t = observed["trace"]
    flushes = len(named(observed["spans"], "cascade.stage2a"))
    if t is None or not flushes:
        return None
    secs = t.op_seconds(is_mq)
    return 1e3 * secs / flushes if secs > 0 else None
