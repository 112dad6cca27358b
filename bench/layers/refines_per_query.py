"""Mean raw refines of a request (``exact_refines`` of its result)."""
from _spans import mean


def read(observed):
    return mean(s["exact_refines"] for s in observed.get("results", []))
