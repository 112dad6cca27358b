"""Device time of the fused d² kernel per distance."""
from _work import is_fused


def read(observed):
    t = observed["trace"]
    n = len(observed["work"].get("n_sel", []))
    if t is None or not n:
        return None
    secs = t.op_seconds(is_fused)
    return 1e3 * secs / n if secs > 0 else None
