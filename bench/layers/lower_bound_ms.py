"""Device time per distance of ProHD's certified lower bound
(``prohd.lower_bound``: the sorts and ``searchsorted`` of ``projected_hd``)."""
from _scopes import per_distance_ms


def read(observed):
    return per_distance_ms(observed, "prohd.lower_bound")
