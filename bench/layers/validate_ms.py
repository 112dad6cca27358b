"""Mean self time of ``hd.validate``: the front door's finiteness check,
which copies both clouds to the host."""
from _spans import mean, self_seconds


def read(observed):
    m = mean(self_seconds(observed["spans"], "hd.validate"))
    return None if m is None else 1e3 * m
