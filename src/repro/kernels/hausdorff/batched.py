"""Pallas TPU kernel: batched masked bucket scan — per-set bidirectional HD
over a whole padded bucket slab in ONE launch.

The corpus cascade's hot loop (``repro.index.cascade`` stages 1/2a) measures
one query cloud against every surviving member of a storage bucket: a
(S, capacity, D) slab of padded sets plus validity masks.  PR 4 served that
with a vmapped pure-JAX scan; this kernel is the RT-HDIST-style native
formulation (ROADMAP open item): grid = (set-slot, A-blocks, B-blocks), the
innermost two axes exactly the PR 1 fused bidirectional scan, the outermost
axis walking the bucket's set slots — S fused bidirectional HDs for the
cost of one kernel launch and one pass over the slab.

Everything that made the single-pair kernel exact carries over unchanged:

- each (Ba, Bb) squared-distance tile ``||q||² − 2 q·bᵀ + ||b||²`` is
  computed ONCE (MXU GEMM) and folded into BOTH accumulators — the per-set
  row mins (query→set) and the per-set col mins (set→query);
- squared norms are hoisted out of the grid and streamed in as operands,
  with row validity (user masks + block padding) folded in as +inf entries
  ("poisoned norms"): an invalid row's d² row/col is +inf and can win
  neither min — no per-element mask selects in-loop;
- the query operands are FETCHED once per (i, j) and shared by every set
  slot (their index maps ignore ``s``), which is the batching win over S
  independent launches.

Per-set early-out (the scalar-prefetch prune gate): two SMEM operands,
``lb`` (S,) — a certified lower bound on the set's distance to the query,
e.g. the store's precomputed projection-interval gaps (stage-0 bounds) —
and ``cut`` (S,) — the caller's cutoff, e.g. the cascade's current τ.  Every
tile of set ``s`` skips its GEMM (``pl.when``) iff ``lb[s] > cut[s]``; the
lane's accumulators then stay +inf, which finalizes to the certified
sentinel +inf ("provably farther than the cutoff") rather than a value.
Lanes the gate does NOT skip are computed by the identical op sequence as a
gate-off launch, so their bits are unchanged (pinned by the conformance
suite); ``cut = +inf`` disables the gate entirely.

Layout: grid = (S, n_q/Ba, cap/Bb), j innermost.  Per-set rows are stored
(S, 1, ·) with the set axis squeezed from each block: the TPU compiler
tiles a block's last two dims by (8, 128) unless they span the array, so
it refuses a (1, Bb) block of an (S, cap) array but accepts one of
(S, 1, cap).  The row-min output block
(1, Ba) at (s, 0, i) stays VMEM-resident across the j sweep; the col-min
output row (1, cap) at (s, 0, 0) stays resident across the whole (i, j)
sweep of its set, each step read-modify-writing its Bb-aligned lane slice.
Both revisit patterns are consecutive, so no output flush races a refetch.

VMEM per step (fp32): q tile Ba·D + b tile Bb·D + d² tile Ba·Bb + norm rows
+ the resident (1, cap) col-min row — bucket capacities are ≤ a few
thousand rows, far inside the budget that forced chunking in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import exact
from repro.core.exact import CERTIFIED_PRECISION
from repro.kernels.hausdorff.ops import _default_interpret, _pad_axis, fit_block

__all__ = [
    "batched_min_sqdists_pallas",
    "batched_min_sqdists_mirror",
    "batched_min_sqdists",
    "batched_bucket_hd",
    "multiquery_min_sqdists_pallas",
    "multiquery_min_sqdists_mirror",
    "multiquery_min_sqdists",
    "multiquery_bucket_hd",
]

_INF = float("inf")  # python float: jnp constants would become kernel consts

# The gate operands ride in scalar-prefetch SMEM, 1 MiB on a v5e for every
# scalar a kernel keeps: at 8 queries × 65536 sets one (Q, S) fp32 gate
# operand alone is 2 MiB, which the TPU compiler refuses.  A launch gates
# at most this many (query, set) lanes; a larger slab runs as several
# launches over consecutive set ranges, each reading the slab in place.
GATE_LANES = 1 << 16


def _batched_kernel(
    lb_ref,      # SMEM (S,): certified lower bound per set slot
    cut_ref,     # SMEM (S,): caller cutoff per set slot (+inf = no gate)
    q_ref,       # (Ba, D) query block — shared across set slots
    b_ref,       # (1, Bb, D) slab block of set s
    q2_ref,      # (Ba, 1) hoisted ||q||²; +inf ⇒ row invalid/padded
    b2_ref,      # (1, Bb) hoisted ||b||²; +inf ⇒ row invalid/padded
    mina_ref,    # out (1, Ba) block of set s — revisited across the j sweep
    minb_ref,    # out (1, cap) row of set s — resident across (i, j)
    *,
    block_b: int,
):
    """One (s, i, j) grid step: fold set s's d² tile into both accumulators."""
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init_rows():
        mina_ref[...] = jnp.full(mina_ref.shape, _INF, dtype=jnp.float32)

    @pl.when((i == 0) & (j == 0))
    def _init_cols():
        minb_ref[...] = jnp.full(minb_ref.shape, _INF, dtype=jnp.float32)

    # Per-set early-out: a gated lane's accumulators stay +inf (a certified
    # "farther than cut" sentinel), never a garbage partial value.
    @pl.when(lb_ref[s] <= cut_ref[s])
    def _compute():
        q = q_ref[...].astype(jnp.float32)    # (Ba, D)
        b = b_ref[0].astype(jnp.float32)      # (Bb, D)
        qb = jax.lax.dot_general(
            q,
            b,
            dimension_numbers=(((1,), (1,)), ((), ())),  # q @ b.T
            precision=CERTIFIED_PRECISION,
            preferred_element_type=jnp.float32,
        )
        # +inf norms poison invalid rows/cols in both directions at once.
        d2 = jnp.maximum(q2_ref[...] - 2.0 * qb + b2_ref[...], 0.0)  # (Ba, Bb)

        tile_row_min = jnp.min(d2, axis=1)[None, :]                  # (1, Ba)
        mina_ref[...] = jnp.minimum(mina_ref[...], tile_row_min)

        tile_col_min = jnp.min(d2, axis=0)[None, :]                  # (1, Bb)
        sl = (slice(None), pl.ds(pl.multiple_of(j * block_b, block_b), block_b))
        minb_ref[sl] = jnp.minimum(minb_ref[sl], tile_col_min)


@functools.partial(jax.jit, static_argnames=("block_a", "block_b", "interpret"))
def batched_min_sqdists_pallas(
    q: jnp.ndarray,
    slab: jnp.ndarray,
    q2: jnp.ndarray,
    b2: jnp.ndarray,
    lb: jnp.ndarray,
    cut: jnp.ndarray,
    *,
    block_a: int,
    block_b: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-launch batched bidirectional min-scan over a bucket slab.

    Preconditions (enforced by :func:`batched_min_sqdists`): ``q`` is
    (n_q_pad, D) with n_q_pad % block_a == 0 and D % 128 == 0 (or small-D
    padded); ``slab`` is (S, cap_pad, D) with cap_pad % block_b == 0;
    ``q2`` (n_q_pad, 1) / ``b2`` (S, 1, cap_pad) are hoisted squared norms
    with +inf at invalid/padded rows; ``lb``/``cut`` are (S,) fp32 gate
    operands in any consistent units (``cut = +inf`` disables the gate).

    Returns ``(min_a, min_b)``: (S, 1, n_q_pad) per-query min d² against
    each set's valid rows, and (S, 1, cap_pad) per-row min d² against the
    valid query rows, both fp32.  Gated-out lanes are +inf throughout;
    rows that are themselves invalid come back +inf and must be masked by
    the caller before any max-reduce.  The unit middle axis is there for
    the TPU tiling (module docstring, "Layout").
    """
    n_q, d = q.shape
    s_sets, cap = slab.shape[0], slab.shape[1]
    parts = []
    for s0 in range(0, s_sets, GATE_LANES):
        n = min(GATE_LANES, s_sets - s0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, n_q // block_a, cap // block_b),
            in_specs=[
                pl.BlockSpec((block_a, d), lambda s, i, j, *_: (i, 0)),
                pl.BlockSpec(
                    (1, block_b, d), lambda s, i, j, *_, s0=s0: (s + s0, j, 0)
                ),
                pl.BlockSpec((block_a, 1), lambda s, i, j, *_: (i, 0)),
                pl.BlockSpec(
                    (None, 1, block_b), lambda s, i, j, *_, s0=s0: (s + s0, 0, j)
                ),
            ],
            out_specs=[
                pl.BlockSpec((None, 1, block_a), lambda s, i, j, *_: (s, 0, i)),
                pl.BlockSpec((None, 1, cap), lambda s, i, j, *_: (s, 0, 0)),
            ],
        )
        parts.append(pl.pallas_call(
            functools.partial(_batched_kernel, block_b=block_b),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((n, 1, n_q), jnp.float32),
                jax.ShapeDtypeStruct((n, 1, cap), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
            ),
            interpret=interpret,
        )(lb[s0:s0 + n], cut[s0:s0 + n], q, slab, q2, b2))
    if len(parts) == 1:
        return parts[0]
    return tuple(jnp.concatenate(p, axis=0) for p in zip(*parts))


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "interpret", "use_pallas")
)
def batched_min_sqdists(
    q: jnp.ndarray,
    slab: jnp.ndarray,
    *,
    valid_q: jnp.ndarray | None = None,
    valid_slab: jnp.ndarray | None = None,
    lb: jnp.ndarray | None = None,
    cut: jnp.ndarray | None = None,
    block_a: int = 512,
    block_b: int = 512,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched bidirectional min scan of one query against a bucket slab.

    q          — (n_q, D) query cloud
    slab       — (S, cap, D) padded bucket slab (one row prefix per set)
    valid_q    — (n_q,) bool, True = real row (None ⇒ all valid)
    valid_slab — (S, cap) bool per-set validity (None ⇒ all valid)
    lb / cut   — (S,) per-set prune-gate operands: set s is computed iff
                 ``lb[s] <= cut[s]`` and left at the +inf sentinel
                 otherwise.  Defaults (0, +inf) disable the gate.
    use_pallas — False routes to :func:`batched_min_sqdists_mirror`, the
                 pure-JAX fallback with identical gate semantics.

    Returns ``(min_a (S, n_q), min_b (S, cap))`` fp32 min squared
    distances; entries of invalid rows (and every entry of gated-out
    lanes) are +inf and must be masked before reduction.
    """
    n_q = q.shape[0]
    s_sets, cap = slab.shape[0], slab.shape[1]
    va = valid_q if valid_q is not None else jnp.ones((n_q,), jnp.bool_)
    vb = valid_slab if valid_slab is not None else jnp.ones((s_sets, cap), jnp.bool_)
    lb = jnp.zeros((s_sets,), jnp.float32) if lb is None else lb.astype(jnp.float32)
    cut = (
        jnp.full((s_sets,), jnp.inf, jnp.float32)
        if cut is None
        else cut.astype(jnp.float32)
    )
    if not use_pallas:
        mina, minb = batched_min_sqdists_mirror(
            q, slab, valid_q=va, valid_slab=vb, lb=lb, cut=cut,
            block_a=block_a, block_b=block_b,
        )
        return mina, minb

    if interpret is None:
        interpret = _default_interpret()
    block_a = fit_block(block_a, n_q)
    block_b = fit_block(block_b, cap)

    q_p = _pad_axis(_pad_axis(q, 128, 1), block_a, 0)
    s_p = _pad_axis(_pad_axis(slab, 128, 2), block_b, 1)
    va_p = _pad_axis(va.astype(jnp.float32)[:, None], block_a, 0)      # (n_q_pad, 1)
    vb_p = _pad_axis(vb.astype(jnp.float32), block_b, 1)               # (S, cap_pad)

    # Zero invalid rows' data (garbage in masked rows must not leak NaN
    # through the GEMM term) and poison their norms (+inf excludes them).
    q_p = jnp.where(va_p > 0.0, q_p, jnp.zeros((), q_p.dtype))
    s_p = jnp.where(vb_p[:, :, None] > 0.0, s_p, jnp.zeros((), s_p.dtype))
    q32 = q_p.astype(jnp.float32)
    s32 = s_p.astype(jnp.float32)
    q2 = jnp.sum(q32 * q32, axis=1, keepdims=True)                     # (n_q_pad, 1)
    b2 = jnp.sum(s32 * s32, axis=2)                                    # (S, cap_pad)
    q2 = jnp.where(va_p > 0.0, q2, jnp.inf)
    b2 = jnp.where(vb_p > 0.0, b2, jnp.inf)

    mina, minb = batched_min_sqdists_pallas(
        q_p, s_p, q2, b2[:, None, :], lb, cut,
        block_a=block_a, block_b=block_b, interpret=interpret,
    )
    return mina[:, 0, :n_q], minb[:, 0, :cap]


@functools.partial(jax.jit, static_argnames=("block_a", "block_b"))
def batched_min_sqdists_mirror(
    q: jnp.ndarray,
    slab: jnp.ndarray,
    *,
    valid_q: jnp.ndarray | None = None,
    valid_slab: jnp.ndarray | None = None,
    lb: jnp.ndarray | None = None,
    cut: jnp.ndarray | None = None,
    block_a: int = 4096,
    block_b: int = 2048,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-JAX mirror of the batched bucket kernel (gate semantics incl.).

    One vmap over the set axis of the PR 1 fused bidirectional scan
    (``exact.fused_min_sqdists_tiled``) — per-lane bits are exactly the
    ``fused_mirror`` backend's, which is what lets this fallback inherit
    the conformance contract verbatim.  The query-side preparation is
    loop-invariant under vmap, so XLA hoists it out of the batch — one
    reason the batched route beats S independent dispatches even without
    Pallas.  Gated-out lanes (``lb > cut``) are forced to the same +inf
    sentinel the kernel leaves behind.
    """
    n_q = q.shape[0]
    s_sets, cap = slab.shape[0], slab.shape[1]
    va = valid_q if valid_q is not None else jnp.ones((n_q,), jnp.bool_)
    vb = valid_slab if valid_slab is not None else jnp.ones((s_sets, cap), jnp.bool_)
    lb = jnp.zeros((s_sets,), jnp.float32) if lb is None else lb.astype(jnp.float32)
    cut = (
        jnp.full((s_sets,), jnp.inf, jnp.float32)
        if cut is None
        else cut.astype(jnp.float32)
    )

    def one(pts, v, l, c):
        ma, mb = exact.fused_min_sqdists_tiled(
            q, pts, valid_a=va, valid_b=v, block_a=block_a, block_b=block_b
        )
        skip = l > c
        return (
            jnp.where(skip, jnp.inf, ma),
            jnp.where(skip, jnp.inf, mb),
        )

    return jax.vmap(one)(slab, vb, lb, cut)


@functools.partial(
    jax.jit,
    static_argnames=("directed", "block_a", "block_b", "interpret", "use_pallas"),
)
def batched_bucket_hd(
    q: jnp.ndarray,
    slab: jnp.ndarray,
    *,
    valid_q: jnp.ndarray | None = None,
    valid_slab: jnp.ndarray | None = None,
    lb: jnp.ndarray | None = None,
    cut: jnp.ndarray | None = None,
    directed: bool = False,
    block_a: int = 512,
    block_b: int = 512,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> jnp.ndarray:
    """(S,) exact (directed) Hausdorff distances of one query vs a slab.

    The per-set reduction of :func:`batched_min_sqdists`: each lane is
    finalized exactly like the single-pair paths (``exact.finalize_mins``
    — empty query side ⇒ 0.0, empty target side ⇒ +inf).  Gated-out lanes
    come back +inf (certified "farther than cut"), except under an
    all-invalid query side whose 0.0 convention dominates.
    """
    mina, minb = batched_min_sqdists(
        q, slab, valid_q=valid_q, valid_slab=valid_slab, lb=lb, cut=cut,
        block_a=block_a, block_b=block_b, interpret=interpret,
        use_pallas=use_pallas,
    )
    vb = (
        valid_slab
        if valid_slab is not None
        else jnp.ones(slab.shape[:2], jnp.bool_)
    )
    h_a = jax.vmap(lambda m: exact.finalize_mins(m, valid_q))(mina)
    if directed:
        return h_a
    h_b = jax.vmap(exact.finalize_mins)(minb, vb)
    return jnp.maximum(h_a, h_b)


# ---------------------------------------------------------------------------
# Multi-query extension: the grid gains a query axis (PR 7).
#
# The batched kernel above shares the QUERY operands across set slots; the
# multi-query kernel additionally shares the SLAB operands across a query
# batch — its slab index map ignores the query coordinate, so a (S, cap, D)
# slab is walked by Q queries inside ONE launch instead of Q launches.  The
# prune gate generalizes to a per-(query, set) scalar-prefetch pair
# ``lb[qq, s] / cut[qq, s]``: each query keeps its OWN certified bounds and
# its OWN cutoff τ_q, and a gated (qq, s) lane stays at the certified +inf
# sentinel exactly as in the single-query kernel.
# ---------------------------------------------------------------------------


def _multiquery_kernel(
    lb_ref,      # SMEM (Q, S): certified lower bound per (query, set) pair
    cut_ref,     # SMEM (Q, S): caller cutoff per (query, set) (+inf = no gate)
    q_ref,       # (1, Ba, D) query block of query qq
    b_ref,       # (1, Bb, D) slab block of set s — shared across queries
    q2_ref,      # (1, Ba, 1) hoisted ||q||²; +inf ⇒ row invalid/padded
    b2_ref,      # (1, Bb) hoisted ||b||²; +inf ⇒ row invalid/padded
    mina_ref,    # out (1, Ba) block of (qq, s) — revisited across j
    minb_ref,    # out (1, cap) row of (qq, s) — resident across (i, j)
    *,
    block_b: int,
):
    """One (qq, s, i, j) grid step: fold query qq's d² tile against set s."""
    qq = pl.program_id(0)
    s = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init_rows():
        mina_ref[...] = jnp.full(mina_ref.shape, _INF, dtype=jnp.float32)

    @pl.when((i == 0) & (j == 0))
    def _init_cols():
        minb_ref[...] = jnp.full(minb_ref.shape, _INF, dtype=jnp.float32)

    # Per-(query, set) early-out: a gated lane's accumulators stay +inf (a
    # certified "farther than this query's cut" sentinel), never garbage.
    @pl.when(lb_ref[qq, s] <= cut_ref[qq, s])
    def _compute():
        q = q_ref[0].astype(jnp.float32)      # (Ba, D)
        b = b_ref[0].astype(jnp.float32)      # (Bb, D)
        qb = jax.lax.dot_general(
            q,
            b,
            dimension_numbers=(((1,), (1,)), ((), ())),  # q @ b.T
            precision=CERTIFIED_PRECISION,
            preferred_element_type=jnp.float32,
        )
        d2 = jnp.maximum(q2_ref[0] - 2.0 * qb + b2_ref[...], 0.0)  # (Ba, Bb)

        tile_row_min = jnp.min(d2, axis=1)[None, :]                # (1, Ba)
        mina_ref[...] = jnp.minimum(mina_ref[...], tile_row_min)

        tile_col_min = jnp.min(d2, axis=0)[None, :]                # (1, Bb)
        sl = (slice(None), pl.ds(pl.multiple_of(j * block_b, block_b), block_b))
        minb_ref[sl] = jnp.minimum(minb_ref[sl], tile_col_min)


@functools.partial(jax.jit, static_argnames=("block_a", "block_b", "interpret"))
def multiquery_min_sqdists_pallas(
    qs: jnp.ndarray,
    slab: jnp.ndarray,
    q2: jnp.ndarray,
    b2: jnp.ndarray,
    lb: jnp.ndarray,
    cut: jnp.ndarray,
    *,
    block_a: int,
    block_b: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-launch multi-query bidirectional min-scan over a bucket slab.

    Preconditions (enforced by :func:`multiquery_min_sqdists`): ``qs`` is
    (Q, n_q_pad, D) with n_q_pad % block_a == 0 and D % 128 == 0; ``slab``
    is (S, cap_pad, D) with cap_pad % block_b == 0; ``q2`` (Q, n_q_pad, 1) /
    ``b2`` (S, 1, cap_pad) are hoisted squared norms with +inf at invalid
    rows; ``lb``/``cut`` are (Q, S) fp32 per-(query, set) gate operands.

    Returns ``(min_a (Q, S, 1, n_q_pad), min_b (Q, S, 1, cap_pad))`` fp32,
    with the same unit axis as :func:`batched_min_sqdists_pallas` and for
    the same tiling reason.  The slab block's index map ignores the query
    coordinate, so consecutive grid steps that differ only in their inner
    sweep reuse the fetched slab block — the query batch shares each slab
    in one launch.
    """
    q_batch, n_q, d = qs.shape
    s_sets, cap = slab.shape[0], slab.shape[1]
    step = max(1, GATE_LANES // q_batch)
    parts = []
    for s0 in range(0, s_sets, step):
        n = min(step, s_sets - s0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(q_batch, n, n_q // block_a, cap // block_b),
            in_specs=[
                pl.BlockSpec((1, block_a, d), lambda qq, s, i, j, *_: (qq, i, 0)),
                pl.BlockSpec(
                    (1, block_b, d),
                    lambda qq, s, i, j, *_, s0=s0: (s + s0, j, 0),
                ),
                pl.BlockSpec((1, block_a, 1), lambda qq, s, i, j, *_: (qq, i, 0)),
                pl.BlockSpec(
                    (None, 1, block_b),
                    lambda qq, s, i, j, *_, s0=s0: (s + s0, 0, j),
                ),
            ],
            out_specs=[
                pl.BlockSpec(
                    (None, None, 1, block_a), lambda qq, s, i, j, *_: (qq, s, 0, i)
                ),
                pl.BlockSpec(
                    (None, None, 1, cap), lambda qq, s, i, j, *_: (qq, s, 0, 0)
                ),
            ],
        )
        parts.append(pl.pallas_call(
            functools.partial(_multiquery_kernel, block_b=block_b),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((q_batch, n, 1, n_q), jnp.float32),
                jax.ShapeDtypeStruct((q_batch, n, 1, cap), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 4
            ),
            interpret=interpret,
        )(lb[:, s0:s0 + n], cut[:, s0:s0 + n], qs, slab, q2, b2))
    if len(parts) == 1:
        return parts[0]
    return tuple(jnp.concatenate(p, axis=1) for p in zip(*parts))


@functools.partial(jax.jit, static_argnames=("block_a", "block_b"))
def multiquery_min_sqdists_mirror(
    qs: jnp.ndarray,
    slab: jnp.ndarray,
    *,
    valid_qs: jnp.ndarray | None = None,
    valid_slab: jnp.ndarray | None = None,
    lb: jnp.ndarray | None = None,
    cut: jnp.ndarray | None = None,
    block_a: int = 4096,
    block_b: int = 2048,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pure-JAX mirror of the multi-query kernel (gate semantics incl.).

    One vmap over the query axis of :func:`batched_min_sqdists_mirror`,
    with the slab operands held constant across the batch — the slab-side
    preparation (norm hoisting, poisoning) is loop-invariant under the
    query vmap, so XLA hoists it out and the batch shares it, mirroring
    the kernel's shared-slab fetch.  Per-lane bits are exactly the
    ``fused_mirror`` backend's.
    """
    q_batch, n_q = qs.shape[0], qs.shape[1]
    s_sets, cap = slab.shape[0], slab.shape[1]
    va = (
        valid_qs
        if valid_qs is not None
        else jnp.ones((q_batch, n_q), jnp.bool_)
    )
    vb = valid_slab if valid_slab is not None else jnp.ones((s_sets, cap), jnp.bool_)
    lb = (
        jnp.zeros((q_batch, s_sets), jnp.float32)
        if lb is None
        else lb.astype(jnp.float32)
    )
    cut = (
        jnp.full((q_batch, s_sets), jnp.inf, jnp.float32)
        if cut is None
        else cut.astype(jnp.float32)
    )

    def one_q(q, v, l, c):
        return batched_min_sqdists_mirror(
            q, slab, valid_q=v, valid_slab=vb, lb=l, cut=c,
            block_a=block_a, block_b=block_b,
        )

    return jax.vmap(one_q)(qs, va, lb, cut)


@functools.partial(
    jax.jit, static_argnames=("block_a", "block_b", "interpret", "use_pallas")
)
def multiquery_min_sqdists(
    qs: jnp.ndarray,
    slab: jnp.ndarray,
    *,
    valid_qs: jnp.ndarray | None = None,
    valid_slab: jnp.ndarray | None = None,
    lb: jnp.ndarray | None = None,
    cut: jnp.ndarray | None = None,
    block_a: int = 512,
    block_b: int = 512,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-query batched bidirectional min scan against a bucket slab.

    qs         — (Q, n_q, D) query batch (one padded row prefix per query)
    slab       — (S, cap, D) padded bucket slab (one row prefix per set)
    valid_qs   — (Q, n_q) bool, True = real row (None ⇒ all valid)
    valid_slab — (S, cap) bool per-set validity (None ⇒ all valid)
    lb / cut   — (Q, S) per-(query, set) prune-gate operands: pair (qq, s)
                 is computed iff ``lb[qq, s] <= cut[qq, s]`` and left at
                 the +inf sentinel otherwise.  Defaults disable the gate.
    use_pallas — False routes to :func:`multiquery_min_sqdists_mirror`.

    Returns ``(min_a (Q, S, n_q), min_b (Q, S, cap))`` fp32 min squared
    distances; entries of invalid rows (and every entry of gated-out
    lanes) are +inf and must be masked before reduction.
    """
    q_batch, n_q = qs.shape[0], qs.shape[1]
    s_sets, cap = slab.shape[0], slab.shape[1]
    va = (
        valid_qs
        if valid_qs is not None
        else jnp.ones((q_batch, n_q), jnp.bool_)
    )
    vb = valid_slab if valid_slab is not None else jnp.ones((s_sets, cap), jnp.bool_)
    lb = (
        jnp.zeros((q_batch, s_sets), jnp.float32)
        if lb is None
        else lb.astype(jnp.float32)
    )
    cut = (
        jnp.full((q_batch, s_sets), jnp.inf, jnp.float32)
        if cut is None
        else cut.astype(jnp.float32)
    )
    if not use_pallas:
        return multiquery_min_sqdists_mirror(
            qs, slab, valid_qs=va, valid_slab=vb, lb=lb, cut=cut,
            block_a=block_a, block_b=block_b,
        )

    if interpret is None:
        interpret = _default_interpret()
    block_a = fit_block(block_a, n_q)
    block_b = fit_block(block_b, cap)

    q_p = _pad_axis(_pad_axis(qs, 128, 2), block_a, 1)
    s_p = _pad_axis(_pad_axis(slab, 128, 2), block_b, 1)
    va_p = _pad_axis(va.astype(jnp.float32)[:, :, None], block_a, 1)  # (Q, n_q_pad, 1)
    vb_p = _pad_axis(vb.astype(jnp.float32), block_b, 1)              # (S, cap_pad)

    # Same prep as the single-query path: zero masked rows' data, poison
    # their norms so they can win neither min.
    q_p = jnp.where(va_p > 0.0, q_p, jnp.zeros((), q_p.dtype))
    s_p = jnp.where(vb_p[:, :, None] > 0.0, s_p, jnp.zeros((), s_p.dtype))
    q32 = q_p.astype(jnp.float32)
    s32 = s_p.astype(jnp.float32)
    q2 = jnp.sum(q32 * q32, axis=2, keepdims=True)                    # (Q, n_q_pad, 1)
    b2 = jnp.sum(s32 * s32, axis=2)                                   # (S, cap_pad)
    q2 = jnp.where(va_p > 0.0, q2, jnp.inf)
    b2 = jnp.where(vb_p > 0.0, b2, jnp.inf)

    mina, minb = multiquery_min_sqdists_pallas(
        q_p, s_p, q2, b2[:, None, :], lb, cut,
        block_a=block_a, block_b=block_b, interpret=interpret,
    )
    return mina[:, :, 0, :n_q], minb[:, :, 0, :cap]


@functools.partial(
    jax.jit,
    static_argnames=("directed", "block_a", "block_b", "interpret", "use_pallas"),
)
def multiquery_bucket_hd(
    qs: jnp.ndarray,
    slab: jnp.ndarray,
    *,
    valid_qs: jnp.ndarray | None = None,
    valid_slab: jnp.ndarray | None = None,
    lb: jnp.ndarray | None = None,
    cut: jnp.ndarray | None = None,
    directed: bool = False,
    block_a: int = 512,
    block_b: int = 512,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> jnp.ndarray:
    """(Q, S) exact (directed) Hausdorff distances of a query batch vs a slab.

    The per-pair reduction of :func:`multiquery_min_sqdists`: each (qq, s)
    lane is finalized exactly like the single-pair paths
    (``exact.finalize_mins`` — empty query side ⇒ 0.0, empty target side ⇒
    +inf).  Gated-out lanes come back +inf (certified "farther than this
    query's cut"), except under an all-invalid query side whose 0.0
    convention dominates.
    """
    mina, minb = multiquery_min_sqdists(
        qs, slab, valid_qs=valid_qs, valid_slab=valid_slab, lb=lb, cut=cut,
        block_a=block_a, block_b=block_b, interpret=interpret,
        use_pallas=use_pallas,
    )
    q_batch, n_q = qs.shape[0], qs.shape[1]
    va = (
        valid_qs
        if valid_qs is not None
        else jnp.ones((q_batch, n_q), jnp.bool_)
    )
    vb = (
        valid_slab
        if valid_slab is not None
        else jnp.ones(slab.shape[:2], jnp.bool_)
    )
    h_a = jax.vmap(
        lambda m, v: jax.vmap(lambda row: exact.finalize_mins(row, v))(m)
    )(mina, va)
    if directed:
        return h_a
    h_b = jax.vmap(lambda m: jax.vmap(exact.finalize_mins)(m, vb))(minb)
    return jnp.maximum(h_a, h_b)
