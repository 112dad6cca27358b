"""Mean wait of a request in the engine: from the start of its
``engine.search`` span to the start of the ``engine.flush`` that carried it."""
from _spans import mean, named


def read(observed):
    start = {s["rid"]: s["t_start"] for s in named(observed["spans"], "engine.search")}
    waits = [f["t_start"] - start[rid]
             for f in named(observed["spans"], "engine.flush")
             for rid in f["attrs"].get("member_rids", []) if rid in start]
    m = mean(waits)
    return None if m is None else 1e3 * m
