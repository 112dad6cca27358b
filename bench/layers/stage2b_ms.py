"""Mean self time of ``cascade.stage2b`` per flush: the raw refines, one
front-door distance each."""
from _spans import mean, self_seconds


def read(observed):
    m = mean(self_seconds(observed["spans"], "cascade.stage2b"))
    return None if m is None else 1e3 * m
