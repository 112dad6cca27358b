"""Plain references for the pairwise Hausdorff cells.

Straightforward ``jax.numpy``, independent of the code under test: it
imports nothing of ``repro`` and takes nothing the program has made.  It
runs on the chip at the cell's own size, in blocks, after the measured
window has closed.

- :func:`nn_sqdists` — every point's squared nearest-neighbour distance to
  the other cloud, both ways, from one blocked pass over all pairs.  The
  exact Hausdorff distance is the square root of their largest entry.
- :func:`centroid_core` — the points ProHD must select whatever else it
  selects: the ``⌊αn⌋ − slack`` extremes at each end of the centroid
  direction (paper Alg. 1).  The largest nearest-neighbour distance over
  them is a floor under any ProHD value.
- :func:`select` — ProHD's selection (paper Alg. 1-3 lines 1-4): the
  rows it keeps along the centroid and the principal directions.  Its
  counts are what every ProHD run at the same fraction keeps.
- :func:`prohd_plain` — ProHD itself (paper Alg. 1-3 with the §II-E
  bound), for the control only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _pad_rows(x, mult: int):
    pad = (-x.shape[0]) % mult
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


@functools.partial(jax.jit, static_argnames=("block_a", "block_b", "dtype"))
def _nn_sqdists_padded(a, b, va, vb, *, block_a, block_b, dtype):
    a = a.astype(dtype).astype(jnp.float32)
    b = b.astype(dtype).astype(jnp.float32)
    an = jnp.where(va, jnp.sum(a * a, axis=1), jnp.inf)
    bn = jnp.where(vb, jnp.sum(b * b, axis=1), jnp.inf)
    ga, gb = a.shape[0] // block_a, b.shape[0] // block_b

    def a_block(i, carry):
        row_min, col_min = carry
        ai = jax.lax.dynamic_slice_in_dim(a, i * block_a, block_a)
        ani = jax.lax.dynamic_slice_in_dim(an, i * block_a, block_a)

        def b_chunk(j, inner):
            rmin, cmin = inner
            bj = jax.lax.dynamic_slice_in_dim(b, j * block_b, block_b)
            bnj = jax.lax.dynamic_slice_in_dim(bn, j * block_b, block_b)
            dot = jnp.matmul(ai.astype(dtype), bj.T.astype(dtype), precision=HIGHEST,
                             preferred_element_type=jnp.float32)
            d2 = jnp.maximum(ani[:, None] + bnj[None, :] - 2.0 * dot, 0.0)
            rmin = jnp.minimum(rmin, d2.min(axis=1))
            cj = jax.lax.dynamic_slice_in_dim(cmin, j * block_b, block_b)
            cmin = jax.lax.dynamic_update_slice_in_dim(
                cmin, jnp.minimum(cj, d2.min(axis=0)), j * block_b, 0)
            return rmin, cmin

        rmin, col_min = jax.lax.fori_loop(
            0, gb, b_chunk, (jnp.full((block_a,), jnp.inf, jnp.float32), col_min))
        row_min = jax.lax.dynamic_update_slice_in_dim(row_min, rmin, i * block_a, 0)
        return row_min, col_min

    return jax.lax.fori_loop(
        0, ga, a_block,
        (jnp.full((a.shape[0],), jnp.inf, jnp.float32),
         jnp.full((b.shape[0],), jnp.inf, jnp.float32)))


def nn_sqdists(a, b, *, block_a: int = 2048, block_b: int = 32768,
               dtype=jnp.float32):
    """``(nn_a, nn_b)``: squared distance from each row of ``a`` to its
    nearest row of ``b``, and from each row of ``b`` to its nearest row of
    ``a``; computed as ``‖a‖² + ‖b‖² − 2·a·bᵀ`` tile by tile, at HIGHEST
    precision on operands rounded to ``dtype``."""
    n_a, n_b = a.shape[0], b.shape[0]
    block_a, block_b = min(block_a, n_a), min(block_b, n_b)
    a32, b32 = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    va = jnp.arange(a32.shape[0] + (-n_a) % block_a) < n_a
    vb = jnp.arange(b32.shape[0] + (-n_b) % block_b) < n_b
    row, col = _nn_sqdists_padded(
        _pad_rows(a32, block_a), _pad_rows(b32, block_b), va, vb,
        block_a=block_a, block_b=block_b, dtype=dtype)
    return row[:n_a], col[:n_b]


def alpha_count(n: int, alpha: float) -> int:
    """``max(1, ⌊αn⌋)`` extremes at each end of a direction (Alg. 1)."""
    return max(1, int(alpha * n))


def centroid_direction(a, b):
    """Unit vector from the centroid of ``a`` to that of ``b`` (Alg. 1)."""
    u = jnp.mean(b, axis=0) - jnp.mean(a, axis=0)
    return u / jnp.linalg.norm(u)


def extremes(proj, k: int):
    """Indices of the ``k`` smallest and ``k`` largest entries."""
    order = jnp.argsort(proj)
    return jnp.concatenate([order[:k], order[-k:]])


def centroid_core(a, b, alpha: float, slack: int = 64):
    """``(core_a, core_b)``: row indices that every ProHD selection at
    fraction ``alpha`` contains: the ``⌊αn⌋ − slack`` extremes at each end
    of the centroid direction, ``slack`` rows short of the cut so that a
    rounding difference at the cut cannot move a row out."""
    u = centroid_direction(a, b)
    out = []
    for x in (a, b):
        proj = jnp.matmul(x, u, precision=HIGHEST)
        out.append(extremes(proj, max(1, alpha_count(x.shape[0], alpha) - slack)))
    return tuple(out)


def _hd_1d(pa, pb):
    pa, pb = jnp.sort(pa), jnp.sort(pb)

    def directed(x, y):
        pos = jnp.searchsorted(y, x, method="sort")
        left = y[jnp.clip(pos - 1, 0, y.shape[0] - 1)]
        right = y[jnp.clip(pos, 0, y.shape[0] - 1)]
        return jnp.max(jnp.minimum(jnp.abs(x - left), jnp.abs(x - right)))

    return jnp.maximum(directed(pa, pb), directed(pb, pa))


def _mm(x, y, dtype):
    return jnp.matmul(x.astype(dtype), y.astype(dtype), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def select(a, b, *, alpha: float, dtype=jnp.float32):
    """ProHD's selection (paper Alg. 1-3): the centroid direction and the
    top ``m = ⌊√D⌋`` principal directions of ``[A; B]``, the ``⌊αn⌋``
    extremes per end along the centroid and ``⌊αn/m⌋`` along each
    principal direction.  ``(idx_a, idx_b, pa, pb)``: the distinct rows
    kept from each cloud and both clouds' projections onto the directions,
    every product at HIGHEST on operands rounded to ``dtype``."""
    d = a.shape[1]
    m = max(1, int(math.isqrt(d)))
    z = jnp.concatenate([a, b])
    zc = z - jnp.mean(z, axis=0)
    _, vecs = jnp.linalg.eigh(_mm(zc.T, zc, dtype))
    dirs = jnp.concatenate([centroid_direction(a, b)[:, None], vecs[:, ::-1][:, :m]], 1)
    pa, pb = _mm(a, dirs, dtype), _mm(b, dirs, dtype)

    def kept(p):
        n = p.shape[0]
        idx = [extremes(p[:, 0], alpha_count(n, alpha))]
        idx += [extremes(p[:, l], alpha_count(n, alpha / m)) for l in range(1, m + 1)]
        return jnp.unique(jnp.concatenate(idx))

    return kept(pa), kept(pb), pa, pb


def prohd_plain(a, b, *, alpha: float = 0.01, inner: str = "full",
                dtype=jnp.float32) -> dict:
    """ProHD (paper Alg. 3) as written: :func:`select`, then
    ``inner="full"``: ``max(h(A_sel→B), h(B_sel→A))``, or
    ``inner="subset"``: ``H(A_sel, B_sel)``.  ``lower`` is ``max_u H_u``
    over the directions' 1-D projections and ``upper`` adds the §II-E bound
    ``2·min_u δ(u)``.  Every product runs at HIGHEST precision on
    operands rounded to ``dtype``."""
    idx_a, idx_b, pa, pb = select(a, b, alpha=alpha, dtype=dtype)
    a_sel, b_sel = a[idx_a], b[idx_b]
    nn = functools.partial(nn_sqdists, dtype=dtype)
    if inner == "full":
        d2 = jnp.maximum(nn(a_sel, b)[0].max(), nn(b_sel, a)[0].max())
    else:
        ra, rb = nn(a_sel, b_sel)
        d2 = jnp.maximum(ra.max(), rb.max())
    lower = jnp.max(jnp.stack([_hd_1d(pa[:, l], pb[:, l]) for l in range(pa.shape[1])]))

    def delta(x, p):
        return jnp.sqrt(jnp.max(jnp.maximum(
            jnp.sum(x * x, axis=1)[:, None] - p * p, 0.0), axis=0))

    bound = 2.0 * jnp.min(jnp.maximum(delta(a, pa), delta(b, pb)))
    return {"value": float(jnp.sqrt(d2)), "lower": float(lower),
            "upper": float(lower + bound),
            "n_sel_a": int(a_sel.shape[0]), "n_sel_b": int(b_sel.shape[0])}


def pair_facts(a, b, *, alpha: float) -> dict:
    """The reference's readings of one pair: the exact Hausdorff distance,
    the floor that the centroid core sets, the pair's scale
    ``max‖a‖ + max‖b‖`` for the fp margin, ``candidates``: every point's
    nearest-neighbour distance from the floor up, sorted, and ``n_sel``:
    how many rows of each cloud ProHD's selection keeps.  A ProHD value
    with ``inner="full"`` is the largest nearest-neighbour distance over
    the points it selected, so it is one of the candidates."""
    nn_a, nn_b = nn_sqdists(a, b)
    core_a, core_b = centroid_core(a, b, alpha)
    core2 = float(jnp.maximum(nn_a[core_a].max(), nn_b[core_b].max()))
    nn = np.concatenate([np.asarray(nn_a), np.asarray(nn_b)]).astype(np.float64)
    cand = np.sqrt(np.sort(nn[nn >= core2 * (1 - 1e-5)]))
    scale = float(jnp.linalg.norm(a, axis=1).max() + jnp.linalg.norm(b, axis=1).max())
    idx_a, idx_b, _, _ = select(a, b, alpha=alpha)
    return {"exact": float(cand[-1]), "core": float(np.sqrt(core2)),
            "scale": scale, "candidates": cand,
            "n_sel": (int(idx_a.shape[0]), int(idx_b.shape[0]))}


def fp_value_margin(dim: int, scale, value):
    """How far two float32 computations of one distance ``value`` may lie
    apart: with ``E = (dim+2)·eps32·scale²`` bounding the d² error,
    ``2·√E`` near zero and ``2·E/(value − √E) + 1e-6`` elsewhere."""
    e = (dim + 2) * float(np.finfo(np.float32).eps) * np.asarray(scale, np.float64) ** 2
    sqrt_e = np.sqrt(e)
    lo = np.maximum(np.asarray(value, np.float64) - sqrt_e, 0.0)
    return np.where(lo > sqrt_e, 2.0 * e / np.maximum(lo, 1e-300), 2.0 * sqrt_e) + 1e-6
