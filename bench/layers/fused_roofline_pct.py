"""The fused kernel's share of its roofline over the window's distances."""
from _work import fused_distance, is_fused, least_seconds


def read(observed):
    t = observed["trace"]
    w = observed["work"]
    if t is None or not w.get("n_sel"):
        return None
    secs = t.op_seconds(is_fused)
    if secs <= 0:
        return None
    least = sum(least_seconds(*fused_distance(a, b, w["n_a"], w["n_b"], w["dim"]),
                              observed["peaks"]) for a, b in w["n_sel"])
    return 100.0 * least / secs
