"""Mean number of queries an ``engine.flush`` carried."""
from _spans import mean, named


def read(observed):
    return mean(f["attrs"]["batch"] for f in named(observed["spans"], "engine.flush"))
