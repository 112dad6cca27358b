"""Device time per distance of ProHD's selection (``prohd.select``:
directions, projections, extreme masks and the gathers of the kept rows)."""
from _scopes import per_distance_ms


def read(observed):
    return per_distance_ms(observed, "prohd.select")
