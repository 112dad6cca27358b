"""Mean self time of ``cascade.stage2a`` per flush: the bucket launches,
their gathers and the host's interval updates."""
from _spans import mean, self_seconds


def read(observed):
    m = mean(self_seconds(observed["spans"], "cascade.stage2a"))
    return None if m is None else 1e3 * m
