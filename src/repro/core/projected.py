"""Projected (1-D) Hausdorff distances — the estimator §II-E actually bounds.

PAPER DISCREPANCY (documented in DESIGN.md §7): Alg. 3 computes the
D-dimensional Hausdorff on the selected subsets, ĥ = max_{a∈A_sel}
min_{b∈B_sel} ||a-b||.  Restricting the *inner min* to B_sel inflates each
min, so this estimator CAN overestimate H(A,B) — the paper's "never
overestimates" theorem (§II-E.5) applies to Ĥ = max_u H_u(A,B), the max of
1-D projected Hausdorff distances, which is what this module computes.

We therefore ship both:
  - the paper-faithful subset estimator (repro.core.prohd, better point
    estimate in practice), and
  - this certified estimator, satisfying
        H_proj ≤ H(A,B) ≤ H_proj + 2·min_u δ(u)
    and monotone in the direction set — property-tested in
    tests/test_properties.py.

1-D directed HD per direction comes from one sort-merge.  The two
projections are concatenated and sorted once, keys with an "is B" flag as
payload.  In sorted order, the nearest element of the other set on each
side of a position lies just outside its run of same-set elements, and a
prefix max and a suffix min carry those two values across each run.  Each
point's distance to the other set is then the smaller of two float32
subtractions, the same two that a binary search would pick, so the result
is exact.  Cost per direction: one sort of N = n_a + n_b keys and two
scans of ⌈log2 N⌉ elementwise passes each, with no gather and no
data-dependent loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["directed_hd_1d", "hd_1d", "projected_hd"]


def _shift(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """``x`` moved ``k`` places along its last axis (towards the end for
    ``k > 0``), the places it leaves filled with ``fill``."""
    cfg = [(0, 0, 0)] * (x.ndim - 1) + [(k, -k, 0)]
    return lax.pad(x, jnp.asarray(fill, x.dtype), cfg)


def _scan(x: jnp.ndarray, op, fill, *, reverse: bool = False) -> jnp.ndarray:
    """Inclusive prefix (suffix where ``reverse``) scan of ``op`` along the
    last axis by doubling: ⌈log2 n⌉ elementwise passes.  ``lax.cummax``
    lowers to a reduce_window that XLA rewrites into ops without the
    caller's named scope; these passes keep it."""
    k = 1
    while k < x.shape[-1]:
        x = op(x, _shift(x, -k if reverse else k, fill))
        k *= 2
    return x


def _merged_directed_hd(pa: jnp.ndarray, pb: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(h(A→B), h(B→A))`` along the last axis of ``pa`` (..., n_a) and
    ``pb`` (..., n_b), by one sort of their merge; leading axes batch."""
    axis = pa.ndim - 1
    v = jnp.concatenate([pa, pb], axis=axis)
    is_b = lax.broadcasted_iota(jnp.int32, v.shape, axis) >= pa.shape[axis]
    # Unstable: how ties between A and B are ordered moves no distance,
    # and a stable sort would carry a third operand, an index.
    v, is_b = lax.sort((v, is_b), dimension=axis, num_keys=1, is_stable=False)
    # Where a run of one set starts, the value before it is the other
    # set's last element; where a run ends, the value after it is the
    # other set's next.  v is sorted, so a prefix max (suffix min) of
    # those marks gives every position its nearest other-set neighbour
    # on the left (right), -inf (+inf) where there is none.
    last = _scan(jnp.where(is_b != _shift(is_b, 1, False), _shift(v, 1, -jnp.inf), -jnp.inf),
                 jnp.maximum, -jnp.inf)
    nxt = _scan(jnp.where(is_b != _shift(is_b, -1, False), _shift(v, -1, jnp.inf), jnp.inf),
                jnp.minimum, jnp.inf, reverse=True)
    nearest = jnp.minimum(v - last, nxt - v)
    h_ab = jnp.max(jnp.where(is_b, -jnp.inf, nearest), axis=axis)
    h_ba = jnp.max(jnp.where(is_b, nearest, -jnp.inf), axis=axis)
    return h_ab, h_ba


def directed_hd_1d(pa: jnp.ndarray, pb: jnp.ndarray) -> jnp.ndarray:
    """max_i min_j |pa_i - pb_j| for 1-D projections (neither need be sorted)."""
    return _merged_directed_hd(pa, pb)[0]


def hd_1d(pa: jnp.ndarray, pb: jnp.ndarray) -> jnp.ndarray:
    """Undirected 1-D Hausdorff H_u for one direction."""
    return jnp.maximum(*_merged_directed_hd(pa, pb))


@jax.jit
def projected_hd(proj_a: jnp.ndarray, proj_b: jnp.ndarray) -> jnp.ndarray:
    """Ĥ = max_u H_u(A,B) over all direction columns.

    proj_a: (n_a, m), proj_b: (n_b, m) — projections of the FULL clouds onto
    the m unit directions (these are already computed during selection, so
    this estimator adds only one sort and two scans per direction).  The
    work is laid out as (m, n_a + n_b), one direction a row.
    """
    return jnp.max(hd_1d(proj_a.T, proj_b.T))
