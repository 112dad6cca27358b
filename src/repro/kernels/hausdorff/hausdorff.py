"""Pallas TPU kernel: fused bidirectional blocked Hausdorff min-distance scan.

This is the paper's "ANN phase" (Faiss FlatL2, k=1) re-thought for the TPU
(DESIGN.md §3): the nearest-neighbour scan ``min_b ||a-b||²`` over a tile is

    d²(i,j) = ||a_i||² - 2 a·bᵀ + ||b_j||²

whose middle term is an (Ba × D) @ (D × Bb) matmul → MXU work at 197
TFLOP/s bf16, instead of the CPU-SIMD/pruning formulations of the original.

Fusion (PR 1): the undirected H(A,B) used to cost two independent directed
launches, each materialising the same Gram tile (once as A·Bᵀ, once as
B·Aᵀ).  The fused kernel computes the (Ba, Bb) squared-distance tile ONCE
per grid step and folds it into *both* accumulators:

  - per-row min  (A→B direction): ``min_j d²(i, j)``
  - per-col min  (B→A direction): ``min_i d²(i, j)``

halving the MXU work of an undirected HD.  The squared norms ``||a||²`` /
``||b||²`` are hoisted out of the grid entirely — computed once by the
ops.py wrapper and streamed in as (·, 1)/(1, ·) operands — so no grid step
recomputes a reduction that is invariant along one grid axis.  Row
validity (both the user's masks and block padding) is folded into those
same norms: an invalid row's norm is +inf, which makes its entire d² row
and column +inf, so it can win neither direction's min.  No per-element
mask selects run inside the grid at all.

Projection pruning (ProHD's own idea, applied inside the kernel): three
scalar-prefetch operands ride in SMEM —

  lb   (gi, gj): certified lower bound on EVERY d² entry of tile (i, j),
                 derived from 1-D projection interval gaps
                 (|π_u a − π_u b| ≤ ||a−b|| for unit u),
  cut_a (gi,):   upper bound on the final row-min of every valid row in
                 a-block i (from a cheap projection-witness pass),
  cut_b (gj,):   same for the col-mins of b-block j.

A tile is skipped — the GEMM never issued, via ``pl.when`` — iff
``lb > cut_a[i] AND lb > cut_b[j]``: every entry of the tile is then
provably larger than an already-known upper bound of every row min *and*
every col min it could touch, so dropping it cannot change either
accumulator (the witness tile itself can never satisfy the condition, so
the true argmin tile is always visited).  Passing ``lb = 0`` disables
pruning; passing ``cut_b = -inf`` makes the col condition vacuous for
directed-only callers (col mins are then garbage and must be ignored).

Layout / tiling:
  grid = (n_a/Ba, n_b/Bb); Ba, Bb multiples of 128 (lane), D padded to a
  multiple of 128 by the ops.py wrapper (zero-padding D is exact for L2).
  The j axis (B tiles) is the innermost grid dimension.

  - row-min output: block (1, Ba) at (0, i) — resident in VMEM across the
    whole j sweep (Pallas "revisiting output" accumulation), initialised
    at j == 0.
  - col-min output: the FULL (1, n_b_pad) row with a constant (0, 0) index
    map, so it stays resident across the entire grid; each step
    read-modify-writes its own (1, Bb) lane slice by ref indexing at the
    Bb-aligned dynamic offset j·Bb.  This avoids non-consecutive
    output-block revisits (i outer ⇒ block (0, j) would be revisited a full
    j-sweep later, racing the output flush against the refetch).

  Both grid dimensions are "arbitrary": i carries the col-min accumulator,
  j carries the row-min accumulator.

VMEM budget per step (fp32, Ba=Bb=512, D≤512):
  a tile 1 MiB + b tile 1 MiB + d² tile 1 MiB + norm rows 4 KiB
  + row-min block 2 KiB + resident col-min row 4·n_b B (1 MiB at
  n_b = 256k) → ≪ 16 MiB.  The ops.py wrapper chunks the b axis at
  MAX_RESIDENT_B columns per launch so arbitrarily large target clouds
  never blow the resident-row budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.exact import CERTIFIED_PRECISION

DEFAULT_BLOCK_A = 512
DEFAULT_BLOCK_B = 512

_INF = float("inf")  # plain python float: jnp constants would be captured as kernel consts


def _fused_kernel(
    lb_ref,      # SMEM (gi, gj): per-tile lower bound on d²
    cuta_ref,    # SMEM (gi,):    row-min upper bound per a-block
    cutb_ref,    # SMEM (gj,):    col-min upper bound per b-block
    a_ref,       # (Ba, D)
    b_ref,       # (Bb, D)
    a2_ref,      # (Ba, 1) hoisted ||a||²; +inf ⇒ row invalid/padded
    b2_ref,      # (1, Bb) hoisted ||b||²; +inf ⇒ col invalid/padded
    mina_ref,    # out (1, Ba) block — revisited across the j sweep
    minb_ref,    # out (1, n_b_pad) — fully resident across the grid
    *,
    block_b: int,
):
    """One (i, j) grid step: fold the d² tile into both min accumulators."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_rows():
        mina_ref[...] = jnp.full(mina_ref.shape, _INF, dtype=jnp.float32)

    @pl.when((i == 0) & (j == 0))
    def _init_cols():
        minb_ref[...] = jnp.full(minb_ref.shape, _INF, dtype=jnp.float32)

    lb = lb_ref[i, j]
    skip = (lb > cuta_ref[i]) & (lb > cutb_ref[j])

    @pl.when(jnp.logical_not(skip))
    def _compute():
        a = a_ref[...].astype(jnp.float32)   # (Ba, D)
        b = b_ref[...].astype(jnp.float32)   # (Bb, D)
        ab = jax.lax.dot_general(
            a,
            b,
            dimension_numbers=(((1,), (1,)), ((), ())),  # a @ b.T
            precision=CERTIFIED_PRECISION,
            preferred_element_type=jnp.float32,
        )
        # +inf norms poison invalid rows/cols: their d² entries are +inf in
        # both reduction directions (no per-element mask selects needed).
        d2 = jnp.maximum(a2_ref[...] - 2.0 * ab + b2_ref[...], 0.0)  # (Ba, Bb)

        # A→B: fold the tile's row mins into the resident row block.
        tile_row_min = jnp.min(d2, axis=1)[None, :]                  # (1, Ba)
        mina_ref[...] = jnp.minimum(mina_ref[...], tile_row_min)

        # B→A: fold the tile's col mins into this tile's lane slice of the
        # resident full col-min row.
        tile_col_min = jnp.min(d2, axis=0)[None, :]                  # (1, Bb)
        sl = (slice(None), pl.ds(pl.multiple_of(j * block_b, block_b), block_b))
        minb_ref[sl] = jnp.minimum(minb_ref[sl], tile_col_min)


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "interpret")
)
def fused_min_sqdists_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    a2: jnp.ndarray,
    b2: jnp.ndarray,
    lb: jnp.ndarray,
    cut_a: jnp.ndarray,
    cut_b: jnp.ndarray,
    *,
    block_a: int = DEFAULT_BLOCK_A,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-launch bidirectional min-scan.

    Preconditions (enforced by ops.py): n_a % block_a == 0, n_b % block_b
    == 0, D % 128 == 0 (or small-D padded); a2 (n_a, 1) / b2 (1, n_b) are
    the hoisted squared norms with +inf at invalid/padded rows; lb is f32
    (n_a/block_a, n_b/block_b); cut_a / cut_b are f32 per-block cutoffs
    (use lb=0 to disable pruning).

    Returns ``(min_a, min_b)``: (n_a,) per-row min d² over valid b and
    (n_b,) per-col min d² over valid a, both fp32.  Rows/cols that are
    themselves invalid come back +inf and must be masked by the caller
    before any max-reduce.
    """
    n_a, d = a.shape
    n_b = b.shape[0]
    grid = (n_a // block_a, n_b // block_b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_a, d), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((block_b, d), lambda i, j, *_: (j, 0)),
            pl.BlockSpec((block_a, 1), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((1, block_b), lambda i, j, *_: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_a), lambda i, j, *_: (0, i)),
            pl.BlockSpec((1, n_b), lambda i, j, *_: (0, 0)),
        ],
    )
    mina, minb = pl.pallas_call(
        functools.partial(_fused_kernel, block_b=block_b),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, n_a), jnp.float32),
            jax.ShapeDtypeStruct((1, n_b), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(lb, cut_a, cut_b, a, b, a2, b2)
    return mina[0], minb[0]
