"""Device time by ProHD phase: the trace's ops, each resolved to the
``jax.named_scope`` it was traced under by the program's own map
(``repro.obs.xla.op_scopes``, built after the window from the programs the
window ran)."""
from __future__ import annotations


def scope_seconds(observed, scope: str) -> float | None:
    """Device self seconds of the ops under ``scope``; ``None`` where the
    program keeps no such map."""
    t = observed.get("trace")
    if t is None:
        return None
    try:
        from repro.obs import xla
    except ImportError:
        return None
    scopes = xla.op_scopes(("prohd.",))
    if not scopes:
        return None
    return sum(v for k, v in t.self_times().items() if scopes.get(xla.bare(k)) == scope)


def per_distance_ms(observed, scope: str) -> float | None:
    n = len(observed["work"].get("n_sel", []))
    secs = scope_seconds(observed, scope)
    return 1e3 * secs / n if n and secs else None
