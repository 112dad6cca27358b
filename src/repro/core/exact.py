"""Exact Hausdorff distance oracles.

Implementations, by role:

- ``directed_hd_dense``: one-shot (n_a, n_b) distance matrix.  O(n_a n_b)
  memory — reference oracle for tests and tiny inputs.
- ``directed_hd_tiled``: lax.scan over B-tiles with a running min.  O(n_a · T)
  memory, GEMM-formulated, squared norms hoisted out of the scan — the
  "ANN-Exact" (Faiss-Flat) analogue and the production fallback where the
  Pallas kernel is not used.  Supports optional projection pruning.
- ``fused_min_sqdists_tiled`` / ``hausdorff_fused_tiled``: the pure-JAX
  mirror of the fused bidirectional Pallas kernel — each (A-tile, B-tile)
  squared-distance block is computed ONCE and folded into both the per-row
  (A→B) and per-col (B→A) running mins, so an undirected H(A,B) costs one
  GEMM pass instead of two.  With prune tables (repro.core.tile_bounds),
  provably-losing tile pairs skip their GEMM via lax.cond.
- ``directed_hd_earlybreak``: EBHD-style early-break double loop via
  lax.while_loop.  Branch-heavy; exists to reproduce the paper's exact
  baselines (EBHD/ZHD family) on CPU, not as a TPU fast path.

All support optional validity masks so they can run on ProHD's padded
fixed-capacity subsets: invalid A-rows are excluded from the outer max,
invalid B-rows from the inner min.  An empty (all-invalid) query side
yields H = 0.0, never NaN.

Distances are computed as ``||a||² - 2 a·b + ||b||²`` in fp32 and clamped at
zero (the GEMM form can go slightly negative under fp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import tile_bounds

__all__ = [
    "finalize_mins",
    "pairwise_sqdist",
    "directed_hd_dense",
    "directed_hd_tiled",
    "directed_hd_earlybreak",
    "fused_min_sqdists_tiled",
    "hausdorff_dense",
    "hausdorff_tiled",
    "hausdorff_fused_tiled",
    "hausdorff_twosweep_tiled",
    "hausdorff_earlybreak",
]

_NEG = -jnp.inf
_POS = jnp.inf

# Precision of every GEMM whose result a certificate rests on: the
# distance GEMM ``a·bᵀ`` here, in the masked paths and in the Pallas
# kernels, and the projections.  The fp margins (``index.cascade.fp_margin``)
# are derived for true fp32 products; a TPU runs an f32 matmul at DEFAULT
# precision as a single bf16 pass, whose ~2⁻⁸ relative error is far
# outside them.  The CPU computes f32 products at any setting.
CERTIFIED_PRECISION = jax.lax.Precision.HIGHEST


def pairwise_sqdist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distances, (n_a, n_b), fp32, clamped ≥ 0."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)
    d2 = a2 - 2.0 * jnp.matmul(
        a, b.T, precision=CERTIFIED_PRECISION, preferred_element_type=jnp.float32
    ) + b2.T
    return jnp.maximum(d2, 0.0)


def finalize_mins(mins, valid) -> jnp.ndarray:
    """max over valid rows → sqrt; an empty query set gives 0.0, not NaN.

    The single home of the empty-set-HD-is-0.0 rule — the Pallas wrapper
    (kernels/hausdorff/ops.py) reuses it so both backends share semantics.
    """
    if valid is not None:
        mins = jnp.where(valid, mins, _NEG)
    return jnp.sqrt(jnp.maximum(jnp.max(mins), 0.0))


def directed_hd_dense(a, b, *, valid_a=None, valid_b=None) -> jnp.ndarray:
    """h(A,B) = max_a min_b ||a-b||, full distance matrix."""
    d2 = pairwise_sqdist(a, b)
    if valid_b is not None:
        d2 = jnp.where(valid_b[None, :], d2, _POS)
    return finalize_mins(jnp.min(d2, axis=1), valid_a)


@functools.partial(jax.jit, static_argnames=("block",))
def directed_hd_tiled(
    a, b, *, valid_a=None, valid_b=None, block: int = 2048, prune_projs=None
) -> jnp.ndarray:
    """h(A,B) via a scan over B tiles with a running per-row min.

    Memory: O(n_a * block).  ``block`` is padded so n_b need not divide it.
    Both squared-norm vectors are hoisted out of the scan (the historical
    version recomputed ``||b||²`` inside every grid step).  With
    ``prune_projs=(proj_a, proj_b)``, B-tiles whose projection-gap lower
    bound exceeds the witness upper bound of every query skip their GEMM.
    """
    n_a = a.shape[0]
    n_b, d = b.shape
    block = min(block, n_b)
    n_tiles = -(-n_b // block)
    b_pad = tile_bounds.pad_rows(b, block)
    vb = valid_b if valid_b is not None else jnp.ones((n_b,), jnp.bool_)
    vb_pad = tile_bounds.pad_rows(vb, block, value=False)

    a32 = a.astype(jnp.float32)
    a2 = jnp.sum(a32 * a32, axis=1)
    # Invalid/padded b rows get a +inf norm: their whole d² column is then
    # +inf and can never win the min — no per-element mask select in-loop.
    # Their data is zeroed too, so NaN/inf garbage in a masked-out row
    # cannot leak through the GEMM term (NaN + inf = NaN).
    b32_pad = jnp.where(vb_pad[:, None], b_pad.astype(jnp.float32), 0.0)
    b_tiles = b32_pad.reshape(n_tiles, block, d)
    b2_pad = jnp.where(vb_pad, jnp.sum(b32_pad * b32_pad, axis=1), _POS)
    b2_tiles = b2_pad.reshape(n_tiles, block)

    if prune_projs is not None:
        proj_a, proj_b = prune_projs
        tables = tile_bounds.prune_tables(
            a, proj_a, valid_a, b, proj_b, vb, n_a, block, directed=True
        )
        # Single query block (gi=1): tile j skippable iff lb[0, j] clears
        # the one row cutoff (cut_b is −inf under directed=True).
        skip_tiles = tile_bounds.skip_mask(tables)[0]

    def tile_min(cur, bt, b2t):
        d2 = a2[:, None] - 2.0 * jnp.matmul(
            a32, bt.astype(jnp.float32).T,
            precision=CERTIFIED_PRECISION, preferred_element_type=jnp.float32
        ) + b2t[None, :]
        d2 = jnp.maximum(d2, 0.0)
        return jnp.minimum(cur, jnp.min(d2, axis=1))

    if prune_projs is not None:

        def body(carry_min, tile):
            bt, b2t, skip = tile
            new_min = jax.lax.cond(
                skip, lambda cur: cur, lambda cur: tile_min(cur, bt, b2t), carry_min
            )
            return new_min, None

        xs = (b_tiles, b2_tiles, skip_tiles)
    else:

        def body(carry_min, tile):
            bt, b2t = tile
            return tile_min(carry_min, bt, b2t), None

        xs = (b_tiles, b2_tiles)

    init = jnp.full((n_a,), _POS, dtype=jnp.float32)
    mins, _ = jax.lax.scan(body, init, xs)
    return finalize_mins(mins, valid_a)


@functools.partial(jax.jit, static_argnames=("block_a", "block_b"))
def fused_min_sqdists_tiled(
    a,
    b,
    *,
    valid_a=None,
    valid_b=None,
    block_a: int = 4096,
    block_b: int = 2048,
    prune_projs=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-JAX mirror of the fused Pallas kernel: one d² pass, both mins.

    Nested lax.scan over (A-tiles ✕ B-tiles); each tile-pair GEMM and its
    (block_a, block_b) d² materialisation happen ONCE and are reduced both
    row-wise (A→B, folded into the inner carry) and col-wise (B→A, folded
    into a running (n_b,) outer carry — memory stays O(n_a + n_b +
    block_a·block_b), same contract as the directed scan).  Returns
    ``(min_a (n_a,), min_b (n_b,))`` fp32; entries of invalid rows are
    +inf.  With ``prune_projs``, tile pairs whose projection lower bound
    clears both witness cutoffs skip the GEMM entirely (lax.cond — a real
    branch under the sequential scan; no cond is emitted when pruning is
    off).
    """
    n_a, d = a.shape
    n_b = b.shape[0]
    block_a = min(block_a, n_a)
    block_b = min(block_b, n_b)
    gi = -(-n_a // block_a)
    gj = -(-n_b // block_b)

    va = valid_a if valid_a is not None else jnp.ones((n_a,), jnp.bool_)
    vb = valid_b if valid_b is not None else jnp.ones((n_b,), jnp.bool_)
    a_pad = tile_bounds.pad_rows(a, block_a)
    b_pad = tile_bounds.pad_rows(b, block_b)
    va_pad = tile_bounds.pad_rows(va, block_a, value=False)
    vb_pad = tile_bounds.pad_rows(vb, block_b, value=False)

    # Zero invalid rows' data (NaN garbage in masked rows must not leak
    # through the GEMM) and poison their norms (+inf excludes them).
    a32 = jnp.where(va_pad[:, None], a_pad.astype(jnp.float32), 0.0)
    b32 = jnp.where(vb_pad[:, None], b_pad.astype(jnp.float32), 0.0)
    a_tiles = a32.reshape(gi, block_a, d)
    b_tiles = b32.reshape(gj, block_b, d)
    # Validity (user mask AND padding) rides in the hoisted norms: +inf
    # poisons the row's/col's every d² entry, replacing in-loop selects.
    a2_tiles = jnp.where(va_pad, jnp.sum(a32 * a32, axis=1), _POS).reshape(gi, block_a)
    b2_tiles = jnp.where(vb_pad, jnp.sum(b32 * b32, axis=1), _POS).reshape(gj, block_b)

    if prune_projs is not None:
        proj_a, proj_b = prune_projs
        tables = tile_bounds.prune_tables(
            a, proj_a, valid_a, b, proj_b, valid_b, block_a, block_b
        )
        skip = tile_bounds.skip_mask(tables)
    else:
        skip = None

    def tile_mins(row_min, at, a2t, bt, b2t):
        d2 = a2t[:, None] - 2.0 * jnp.matmul(
            at, bt.T, precision=CERTIFIED_PRECISION,
            preferred_element_type=jnp.float32
        ) + b2t[None, :]
        d2 = jnp.maximum(d2, 0.0)
        row_min = jnp.minimum(row_min, jnp.min(d2, axis=1))
        col_tile = jnp.min(d2, axis=0)
        return row_min, col_tile

    if gi == 1 and gj == 1 and prune_projs is None:
        # Single tile pair: the scan would run exactly one step whose
        # carries start at +inf, and ``min(+inf, x) == x`` bitwise — so the
        # loop machinery can be elided without moving a bit (the block
        # layout invariance the conformance harness pins).  This keeps the
        # hot vmapped-bucket case (every slab lane is one tile) free of
        # per-lane lax.scan overhead.
        row_min, col_min = tile_mins(
            jnp.full((block_a,), _POS, jnp.float32),
            a_tiles[0], a2_tiles[0], b_tiles[0], b2_tiles[0],
        )
        return row_min[:n_a], col_min[:n_b]

    def inner(carry, tile):
        row_min = carry
        if skip is None:
            at, a2t, bt, b2t = tile
            row_min, col_tile = tile_mins(row_min, at, a2t, bt, b2t)
        else:
            at, a2t, bt, b2t, sk = tile
            row_min, col_tile = jax.lax.cond(
                sk,
                lambda rm: (rm, jnp.full((block_b,), _POS, jnp.float32)),
                lambda rm: tile_mins(rm, at, a2t, bt, b2t),
                row_min,
            )
        return row_min, col_tile

    def outer(col_min, itile):
        if skip is None:
            at, a2t = itile
            xs = (b_tiles, b2_tiles)
        else:
            at, a2t, skip_row = itile
            xs = (b_tiles, b2_tiles, skip_row)
        row_init = jnp.full((block_a,), _POS, jnp.float32)
        row_min, col_tiles = jax.lax.scan(
            lambda c, t: inner(c, (at, a2t) + t), row_init, xs
        )
        # col_tiles: (gj, block_b) partial col-mins of THIS A-tile — fold
        # into the running accumulator so nothing (gi)-sized materialises.
        return jnp.minimum(col_min, col_tiles), row_min

    itiles = (a_tiles, a2_tiles) if skip is None else (a_tiles, a2_tiles, skip)
    col_init = jnp.full((gj, block_b), _POS, jnp.float32)
    min_b_fold, row_blocks = jax.lax.scan(outer, col_init, itiles)
    min_a = row_blocks.reshape(gi * block_a)[:n_a]
    min_b = min_b_fold.reshape(gj * block_b)[:n_b]
    return min_a, min_b


def directed_hd_earlybreak(a, b) -> jnp.ndarray:
    """EBHD-flavoured exact directed HD (Taha & Hanbury 2015).

    Outer fori over A; inner while_loop over B breaks as soon as a b closer
    than the current global max is found (that a cannot raise the max).
    Correct on any backend; intended as a CPU baseline only.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    n_a, n_b = a.shape[0], b.shape[0]

    def outer(i, cmax):
        ai = a[i]

        def cond(state):
            j, best = state
            return (j < n_b) & (best > cmax)

        def inner(state):
            j, best = state
            d2 = jnp.sum((ai - b[j]) ** 2)
            return j + 1, jnp.minimum(best, d2)

        _, best = jax.lax.while_loop(cond, inner, (0, _POS))
        # best <= cmax means we early-broke: point i cannot increase the max.
        return jnp.where(best > cmax, best, cmax)

    cmax = jax.lax.fori_loop(0, n_a, outer, jnp.float32(0.0))
    return jnp.sqrt(cmax)


def hausdorff_dense(a, b, *, valid_a=None, valid_b=None) -> jnp.ndarray:
    return jnp.maximum(
        directed_hd_dense(a, b, valid_a=valid_a, valid_b=valid_b),
        directed_hd_dense(b, a, valid_a=valid_b, valid_b=valid_a),
    )


def hausdorff_fused_tiled(
    a,
    b,
    *,
    valid_a=None,
    valid_b=None,
    block_a: int = 1024,
    block_b: int = 2048,
    prune_projs=None,
) -> jnp.ndarray:
    """Undirected H(A,B) in one fused GEMM pass (see fused_min_sqdists_tiled)."""
    min_a, min_b = fused_min_sqdists_tiled(
        a, b, valid_a=valid_a, valid_b=valid_b,
        block_a=block_a, block_b=block_b, prune_projs=prune_projs,
    )
    return jnp.maximum(finalize_mins(min_a, valid_a), finalize_mins(min_b, valid_b))


def hausdorff_tiled(a, b, *, valid_a=None, valid_b=None, block: int = 2048) -> jnp.ndarray:
    """Undirected H(A,B), tiled.  Delegates to the fused single-pass scan
    (one GEMM per tile pair instead of the historical two)."""
    return hausdorff_fused_tiled(
        a, b, valid_a=valid_a, valid_b=valid_b, block_a=block, block_b=block
    )


def hausdorff_twosweep_tiled(
    a, b, *, valid_a=None, valid_b=None, block: int = 2048
) -> jnp.ndarray:
    """Historical two-directed-sweep formulation (every Gram tile computed
    twice).  Kept as the benchmark baseline for the fused path."""
    return jnp.maximum(
        directed_hd_tiled(a, b, valid_a=valid_a, valid_b=valid_b, block=block),
        directed_hd_tiled(b, a, valid_a=valid_b, valid_b=valid_a, block=block),
    )


def hausdorff_earlybreak(a, b) -> jnp.ndarray:
    return jnp.maximum(directed_hd_earlybreak(a, b), directed_hd_earlybreak(b, a))
