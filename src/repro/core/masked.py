"""Masked (padding-tolerant) ProHD: estimate + certificate on padded clouds.

The serving layer, the corpus index and the drift monitor all operate on
fixed-capacity padded buffers with row-validity masks (the price of
compile-once batching under jit/vmap).  ProHD's reference implementation
(``repro.core.prohd``) deliberately rejects masks — its selection math is
derived for full clouds — so the masked variant lives here, built from the
same primitives, with every step made validity-aware:

- centroid / PCA directions from masked moments (invalid rows contribute
  zero weight to the mean and the Gram matrix);
- α-extreme selection per direction with invalid rows pushed out of both
  tails (±BIG sentinels), exactly the scheme ``repro.serve`` has always
  used;
- 1-D projected Hausdorff with invalid target rows sorted out of the
  searchsorted window and invalid query rows excluded from the max — this
  replaces the historical serve-layer shortcut of zero-filling invalid
  projections, which injected a phantom point at the origin into every
  1-D cloud and silently broke the §II-E certificate;
- the additive bound's per-direction δ over valid rows only.

``masked_prohd_certified`` returns the paper's full triple: the subset
point estimate ``hd`` (full-inner, so it never overestimates — §II-E.5),
the certified lower bound ``lower = max_u H_u``, and the certified upper
bound ``upper = lower + 2·min_u δ(u)`` (Eq. 5).  All three are exact
functions of the VALID rows only: any padding layout gives the same
answers.

Everything is shape-static and jit/vmap-friendly; the corpus cascade
(``repro.index``) vmaps ``masked_prohd_certified`` across the candidate
axis of each storage bucket.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import exact, projections, selection

__all__ = [
    "MaskedCertificate",
    "EXACT_MASKED_BACKENDS",
    "BATCHED_NATIVE_BACKENDS",
    "MULTIQUERY_NATIVE_BACKENDS",
    "masked_exact_hd",
    "masked_exact_hd_batched",
    "masked_exact_hd_multiquery",
    "masked_centroid",
    "masked_direction_set",
    "masked_projected_hd",
    "masked_additive_bound",
    "masked_prohd_certified",
]

# Same large-but-finite sentinel as tile_bounds: ±inf would poison interval
# arithmetic (inf − inf = NaN) in all-invalid corner cases.
_BIG = 1e30


# ---------------------------------------------------------------------------
# exact masked reductions — the padded mirrors of the raw oracles
# ---------------------------------------------------------------------------
#
# Each backend below computes the EXACT set distance of (possibly padded)
# clouds using the same op sequence as its raw front-door counterpart, with
# validity folded in as zeroed rows + +inf-poisoned norms.  The contract —
# pinned empirically by the conformance harness (tests/conformance/) and
# relied on by the cascade's batched stage-2 tightening — is layered:
#
#   * DETERMINISM: identical inputs at identical shapes give identical
#     bits — min/max reductions are exact, +inf entries lose every min
#     exactly, and retiling (block sizes) only reassociates exact mins, so
#     block layout provably cannot move a bit.  Under vmap, lane results
#     are invariant to batch size and composition.
#   * ACROSS GEMM SHAPES (raw n vs padded capacity, batched vs unbatched
#     matmul, backend formulation ``(a²−2ab)+b²`` vs ``(b²−2ba)+a²``),
#     bitwise equality is NOT a contract: XLA may lower different shapes
#     through different kernels whose contraction rounding differs — the
#     harness records a real one-ulp CPU counterexample on cancellation-
#     heavy data.  What IS certified is the pinned fp margin
#     ``2·sqrt((D+2)·eps32)·scale`` (``repro.index.cascade.fp_margin``):
#     every formulation lands within it of the float64 truth, hence any
#     two land within 2× it of each other.  Same-shape padded-vs-raw
#     equality does hold bitwise across the harness's whole CPU sweep; the
#     cascade deliberately does not lean on it.
#
# Empty-side conventions (shared with ``exact.finalize_mins``): an
# all-invalid QUERY side reduces to 0.0; an all-invalid TARGET side leaves
# every nearest-distance at +inf (the sup-distance to an empty set).


def _masked_exact_dense(a, b, valid_a, valid_b, *, directed, block_a, block_b):
    del block_a, block_b  # dense is one unblocked GEMM per direction
    if directed:
        return exact.directed_hd_dense(a, b, valid_a=valid_a, valid_b=valid_b)
    return exact.hausdorff_dense(a, b, valid_a=valid_a, valid_b=valid_b)


def _masked_exact_tiled(a, b, valid_a, valid_b, *, directed, block_a, block_b):
    if directed:
        return exact.directed_hd_tiled(
            a, b, valid_a=valid_a, valid_b=valid_b, block=block_b
        )
    return exact.hausdorff_fused_tiled(
        a, b, valid_a=valid_a, valid_b=valid_b, block_a=block_a, block_b=block_b
    )


def _masked_exact_fused_mirror(a, b, valid_a, valid_b, *, directed, block_a, block_b):
    min_a, min_b = exact.fused_min_sqdists_tiled(
        a, b, valid_a=valid_a, valid_b=valid_b, block_a=block_a, block_b=block_b
    )
    h = exact.finalize_mins(min_a, valid_a)
    if directed:
        return h
    return jnp.maximum(h, exact.finalize_mins(min_b, valid_b))


def _masked_exact_batched(
    a, b, valid_a, valid_b, *, directed, block_a, block_b, use_pallas
):
    """Single-pair view of the batched bucket kernel: a slab of one set.

    Under an outer vmap the slab axis batches like any other operand —
    Pallas's batching rule folds it into the kernel grid — so the same
    entry serves both the conformance sweep's unbatched calls and the
    cascade's vmapped lanes.
    """
    from repro.kernels.hausdorff import batched

    vb = None if valid_b is None else valid_b[None]
    return batched.batched_bucket_hd(
        a, b[None], valid_q=valid_a, valid_slab=vb, directed=directed,
        block_a=block_a, block_b=block_b, use_pallas=use_pallas,
    )[0]


_masked_exact_batched_pallas = functools.partial(
    _masked_exact_batched, use_pallas=True
)
_masked_exact_batched_mirror = functools.partial(
    _masked_exact_batched, use_pallas=False
)


def _masked_exact_multiquery(
    a, b, valid_a, valid_b, *, directed, block_a, block_b, use_pallas
):
    """Single-pair view of the multi-query bucket kernel: Q=1, S=1.

    Registering the query-axis kernel through the same single-pair adapter
    shape as ``_masked_exact_batched`` is what lets the ENTIRE conformance
    sweep (padded-vs-raw bitwise, vmap-lane invariance, cross-backend
    margins) certify the new lanes without a line of new harness code.
    Under an outer vmap both unit axes batch like any other operand.
    """
    from repro.kernels.hausdorff import batched

    va = None if valid_a is None else valid_a[None]
    vb = None if valid_b is None else valid_b[None]
    return batched.multiquery_bucket_hd(
        a[None], b[None], valid_qs=va, valid_slab=vb, directed=directed,
        block_a=block_a, block_b=block_b, use_pallas=use_pallas,
    )[0, 0]


_masked_exact_multiquery_pallas = functools.partial(
    _masked_exact_multiquery, use_pallas=True
)
_masked_exact_multiquery_mirror = functools.partial(
    _masked_exact_multiquery, use_pallas=False
)


# Registry the conformance harness sweeps: name -> masked exact reduction.
# "dense" and "tiled" mirror the front door's exact/dense and exact/tiled
# dispatches op-for-op (the batched cascade leans on that); "fused_mirror"
# is the raw min-vector reduction of the fused Pallas kernel's pure-JAX
# mirror, kept distinct so single-pass kernels inherit the same contract.
# "batched_pallas" is the batched bucket kernel (native on TPU,
# interpret-mode elsewhere — a testing path, never picked by auto) and
# "batched_mirror" its pure-JAX fallback (the production CPU/GPU batched
# route); both are served by kernels/hausdorff/batched.py.
EXACT_MASKED_BACKENDS = {
    "dense": _masked_exact_dense,
    "tiled": _masked_exact_tiled,
    "fused_mirror": _masked_exact_fused_mirror,
    "batched_pallas": _masked_exact_batched_pallas,
    "batched_mirror": _masked_exact_batched_mirror,
    "multiquery_pallas": _masked_exact_multiquery_pallas,
    "multiquery_mirror": _masked_exact_multiquery_mirror,
}

# Backends with a NATIVE batched (slab-axis) formulation: one launch per
# bucket with an in-kernel per-set prune gate, instead of an outer vmap.
BATCHED_NATIVE_BACKENDS = ("batched_pallas", "batched_mirror")

# Backends with a NATIVE multi-query (query-axis × slab-axis) formulation:
# one launch measures a whole query batch against a whole bucket slab with
# a per-(query, set) prune gate.  "multiquery_pallas" is the query-axis
# grid kernel (native on TPU, interpret-mode elsewhere — a testing path,
# never picked by auto off-TPU); "multiquery_mirror" the pure-JAX fallback
# (the production CPU/GPU multi-query route).
MULTIQUERY_NATIVE_BACKENDS = ("multiquery_pallas", "multiquery_mirror")


def masked_exact_hd(
    a,
    b,
    *,
    valid_a=None,
    valid_b=None,
    directed: bool = False,
    backend: str = "dense",
    block_a: int = 2048,
    block_b: int = 2048,
) -> jnp.ndarray:
    """EXACT (directed) Hausdorff distance of padded masked clouds.

    Exact arithmetic over the valid rows only — any padding layout yields
    the same value up to GEMM-shape rounding, which the conformance
    harness pins to ``fp_margin`` (bitwise wherever shapes agree).  Safe
    to vmap over a storage bucket's candidate axis — exactly what the
    cascade's batched stage-2 tightening does.
    """
    try:
        impl = EXACT_MASKED_BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown masked exact backend {backend!r}; expected one of "
            f"{tuple(EXACT_MASKED_BACKENDS)}"
        ) from None
    return impl(
        a, b, valid_a, valid_b, directed=directed, block_a=block_a, block_b=block_b
    )


def masked_exact_hd_batched(
    q,
    slab,
    *,
    valid_q=None,
    valid_slab=None,
    lb=None,
    cut=None,
    directed: bool = False,
    backend: str = "batched_mirror",
    block_a: int = 2048,
    block_b: int = 2048,
) -> jnp.ndarray:
    """(S,) EXACT (directed) HD of one query vs a whole padded bucket slab.

    THE bucket-granularity entry: the cascade's stage 2a measures each
    surviving bucket's frontier through it (stage 1 rides the same
    ``backend`` name through ``masked_prohd_certified``'s exact subset
    passes).  ``backend`` names any registered masked exact backend:

    - :data:`BATCHED_NATIVE_BACKENDS` (``batched_pallas`` /
      ``batched_mirror``) run the slab natively — one fused launch (or one
      vmapped fused scan) for the whole bucket, honouring the per-set
      prune gate ``lb``/``cut`` in-kernel (gated-out lanes return the
      certified +inf sentinel; ``cut=None`` disables the gate);
    - every other backend (``dense``/``tiled``/``fused_mirror``) is
      vmapped over the slab axis, with the gate applied as a lane select
      on the results — same semantics, per-pair op sequence.

    Per-lane values carry the conformance contract of the chosen backend:
    invariant to batch size/composition, within ``fp_value_margin`` of any
    raw recomputation.
    """
    s_sets, cap = slab.shape[0], slab.shape[1]
    if backend in BATCHED_NATIVE_BACKENDS:
        from repro.kernels.hausdorff import batched

        return batched.batched_bucket_hd(
            q, slab, valid_q=valid_q, valid_slab=valid_slab, lb=lb, cut=cut,
            directed=directed, block_a=block_a, block_b=block_b,
            use_pallas=(backend == "batched_pallas"),
        )
    if backend in MULTIQUERY_NATIVE_BACKENDS:
        # Q=1 view of the query-axis kernel — this is what lets the
        # multi-query backends serve as rungs of the cascade's fallback
        # ladder with the exact same gate semantics.
        vals = masked_exact_hd_multiquery(
            q[None], slab,
            valid_qs=None if valid_q is None else valid_q[None],
            valid_slab=valid_slab,
            lb=None if lb is None else jnp.asarray(lb)[None],
            cut=None if cut is None else jnp.asarray(cut)[None],
            directed=directed, backend=backend,
            block_a=block_a, block_b=block_b,
        )
        return vals[0]
    vb = valid_slab if valid_slab is not None else jnp.ones((s_sets, cap), jnp.bool_)

    def one(p, v):
        return masked_exact_hd(
            q, p, valid_a=valid_q, valid_b=v, directed=directed,
            backend=backend, block_a=block_a, block_b=block_b,
        )

    vals = jax.vmap(one)(slab, vb)
    if cut is not None:
        lb_ = jnp.zeros((s_sets,), jnp.float32) if lb is None else lb
        # Same corner precedence as the native kernel: under ``directed`` an
        # all-invalid query side's 0.0 convention dominates the gated-out
        # +inf sentinel (undirected keeps +inf — the set→query direction's
        # empty-target convention wins the max).
        empty_q = (
            jnp.logical_not(jnp.any(valid_q)) if valid_q is not None else False
        )
        sentinel = jnp.where(jnp.logical_and(directed, empty_q), 0.0, jnp.inf)
        vals = jnp.where(lb_ > cut, sentinel, vals)
    return vals


def masked_exact_hd_multiquery(
    qs,
    slab,
    *,
    valid_qs=None,
    valid_slab=None,
    lb=None,
    cut=None,
    directed: bool = False,
    backend: str = "multiquery_mirror",
    block_a: int = 2048,
    block_b: int = 2048,
) -> jnp.ndarray:
    """(Q, S) EXACT (directed) HD of a query batch vs a padded bucket slab.

    The multi-query cascade's stage-2a entry (``repro.index.multiquery``):
    one call measures every (query, candidate) frontier pair of a bucket.
    ``backend`` names any registered masked exact backend:

    - :data:`MULTIQUERY_NATIVE_BACKENDS` run the whole (Q, S) block
      natively — the query-axis grid kernel (or its mirror) shares each
      slab block across the query batch in one launch, honouring the
      per-(query, set) prune gate ``lb``/``cut`` (Q, S) in-kernel
      (gated-out lanes return the certified +inf sentinel);
    - every other backend is vmapped over the query axis of
      :func:`masked_exact_hd_batched` — same semantics, per-pair op
      sequence, so any future backend is multi-query-servable for free.

    Per-lane values carry the conformance contract of the chosen backend.
    """
    q_batch, n_q = qs.shape[0], qs.shape[1]
    s_sets = slab.shape[0]
    if backend in MULTIQUERY_NATIVE_BACKENDS:
        from repro.kernels.hausdorff import batched

        return batched.multiquery_bucket_hd(
            qs, slab, valid_qs=valid_qs, valid_slab=valid_slab, lb=lb,
            cut=cut, directed=directed, block_a=block_a, block_b=block_b,
            use_pallas=(backend == "multiquery_pallas"),
        )
    va = (
        valid_qs
        if valid_qs is not None
        else jnp.ones((q_batch, n_q), jnp.bool_)
    )
    lb_ = (
        jnp.zeros((q_batch, s_sets), jnp.float32)
        if lb is None
        else jnp.asarray(lb, jnp.float32)
    )
    cut_ = (
        jnp.full((q_batch, s_sets), jnp.inf, jnp.float32)
        if cut is None
        else jnp.asarray(cut, jnp.float32)
    )

    def one_q(q, v, l, c):
        return masked_exact_hd_batched(
            q, slab, valid_q=v, valid_slab=valid_slab, lb=l, cut=c,
            directed=directed, backend=backend,
            block_a=block_a, block_b=block_b,
        )

    return jax.vmap(one_q)(qs, va, lb_, cut_)


def masked_centroid(points: jnp.ndarray, valid_f: jnp.ndarray) -> jnp.ndarray:
    """Mean over valid rows; ``valid_f`` is the float mask (n,)."""
    s = jnp.sum(points * valid_f[:, None], axis=0)
    return s / jnp.maximum(jnp.sum(valid_f), 1.0)


def masked_direction_set(a, va_f, b, vb_f, m: int) -> jnp.ndarray:
    """Centroid direction + top-m masked-Gram PCA directions, (D, m+1).

    The masked analogue of ``projections.direction_set``: means and the
    Gram matrix accumulate valid rows only (invalid rows are zero-weighted,
    which for the Gram equals dropping them).
    """
    ca = masked_centroid(a, va_f)
    cb = masked_centroid(b, vb_f)
    u0 = cb - ca
    norm = jnp.linalg.norm(u0)
    e1 = jnp.zeros_like(u0).at[0].set(1.0)
    u0 = jnp.where(norm < 1e-9, e1, u0 / jnp.maximum(norm, 1e-9))

    z = jnp.concatenate([a, b])
    vz = jnp.concatenate([va_f, vb_f])
    mean = jnp.sum(z * vz[:, None], axis=0) / jnp.maximum(jnp.sum(vz), 1.0)
    zc = (z - mean) * vz[:, None]
    gram = jnp.matmul(zc.T, zc, preferred_element_type=jnp.float32)
    _, v = jnp.linalg.eigh(gram)  # ascending
    return jnp.concatenate([u0[:, None], v[:, ::-1][:, :m]], axis=1)


def _masked_directed_hd_1d(pa, va, pb, vb) -> jnp.ndarray:
    """max over valid a of min over valid b of |pa − pb| (fixed shapes).

    Invalid targets are +BIG-sentineled so they sort to the tail; candidate
    indices are clipped into the valid prefix, so every query measures a
    REAL valid target.  Invalid queries contribute −inf to the max.  The
    result is clamped at 0, which also covers the degenerate all-invalid
    sides (a distance is nonnegative, and the empty-set directed HD is 0.0
    by the same convention as ``exact.finalize_mins``).
    """
    pbv = jnp.where(vb, pb.astype(jnp.float32), _BIG)
    pbs = jnp.sort(pbv)
    n_valid = jnp.sum(vb.astype(jnp.int32))
    hi = jnp.maximum(n_valid - 1, 0)
    pos = jnp.searchsorted(pbs, pa.astype(jnp.float32))
    left = pbs[jnp.clip(pos - 1, 0, hi)]
    right = pbs[jnp.clip(pos, 0, hi)]
    nearest = jnp.minimum(jnp.abs(pa - left), jnp.abs(pa - right))
    nearest = jnp.where(va, nearest, -jnp.inf)
    # n_valid == 0 leaves only ±BIG sentinels to measure against; force the
    # empty-target convention rather than returning a sentinel-sized "gap".
    return jnp.where(n_valid > 0, jnp.maximum(jnp.max(nearest), 0.0), 0.0)


def masked_projected_hd(proj_a, valid_a, proj_b, valid_b, *, directed: bool = False):
    """max_u H_u over direction columns, valid rows only — certified ≤ H.

    ``directed=True`` keeps only the A→B sweep (certified ≤ h(A→B)).
    """
    fwd = jax.vmap(_masked_directed_hd_1d, in_axes=(1, None, 1, None))(
        proj_a, valid_a, proj_b, valid_b
    )
    if directed:
        return jnp.max(fwd)
    bwd = jax.vmap(_masked_directed_hd_1d, in_axes=(1, None, 1, None))(
        proj_b, valid_b, proj_a, valid_a
    )
    return jnp.max(jnp.maximum(fwd, bwd))


def _masked_delta(points, projs, valid) -> jnp.ndarray:
    """Per-direction max orthogonal deviation over VALID rows, (m,)."""
    p32 = points.astype(jnp.float32)
    sq_norms = jnp.sum(p32 * p32, axis=1, keepdims=True)
    orth_sq = jnp.maximum(sq_norms - projs.astype(jnp.float32) ** 2, 0.0)
    orth_sq = jnp.where(valid[:, None], orth_sq, -jnp.inf)
    return jnp.sqrt(jnp.maximum(jnp.max(orth_sq, axis=0), 0.0))


def masked_additive_bound(a, proj_a, valid_a, b, proj_b, valid_b) -> jnp.ndarray:
    """2 · min_u max(δ_A(u), δ_B(u)) over valid rows (Eq. 5, masked)."""
    da = _masked_delta(a, proj_a, valid_a)
    db = _masked_delta(b, proj_b, valid_b)
    return 2.0 * jnp.min(jnp.maximum(da, db))


class MaskedCertificate(NamedTuple):
    """ProHD estimate + §II-E certificate on masked clouds.

    ``hd`` (full-inner subset estimate) and ``lower`` (max_u H_u) are BOTH
    certified lower bounds on the true masked H; ``upper`` bounds it from
    above.  For directed queries the same holds against h(A→B).
    """

    hd: jnp.ndarray
    lower: jnp.ndarray
    upper: jnp.ndarray


def _select_extreme_mask(proj, valid, m: int, k_centroid: int, k_pca: int):
    """Union of per-direction α-extreme masks, invalid rows excluded."""
    mask = jnp.zeros(proj.shape[:1], bool)
    for col in range(proj.shape[1]):
        k = k_centroid if col == 0 else k_pca
        hi = jnp.where(valid, proj[:, col], -_BIG)
        lo = jnp.where(valid, proj[:, col], _BIG)
        mask |= selection.extreme_mask(hi, k) & valid
        mask |= selection.extreme_mask(-lo, k) & valid
    return mask


def masked_prohd_certified(
    a,
    valid_a,
    b,
    valid_b,
    *,
    alpha: float,
    m: int,
    directed: bool = False,
    block: int = 2048,
    backend: str = "tiled",
) -> MaskedCertificate:
    """Full masked ProHD pass: subset estimate + certified interval.

    a: (n_a, D) with (n_a,) bool ``valid_a`` (True = real row); same for b.
    ``alpha``/``m`` as in ``ProHDConfig`` (k counts are derived from the
    PADDED sizes — static under jit; a looser α on a sparse buffer only
    selects more rows, never fewer, so the certificate is unaffected).
    ``backend`` picks the registered masked exact reduction for the subset
    estimate's directed passes (``EXACT_MASKED_BACKENDS``; the default
    preserves the historical ``tiled`` bits) — the cascade threads its
    resolved bucket backend through here so stage 1 rides the same kernel
    as stage 2a.  Any exact backend keeps ``hd`` a certified lower bound;
    cross-backend drift is within ``fp_value_margin`` (conformance-pinned)
    and absorbed by the cascade's certified margins.
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    va_f = valid_a.astype(jnp.float32)
    vb_f = valid_b.astype(jnp.float32)
    n_a, _ = a.shape
    n_b = b.shape[0]

    dirs = masked_direction_set(a, va_f, b, vb_f, m)
    proj_a = projections.project(a, dirs)
    proj_b = projections.project(b, dirs)

    k_a = selection.alpha_count(n_a, alpha)
    k_b = selection.alpha_count(n_b, alpha)
    k_a_pca = max(1, k_a // max(m, 1))
    k_b_pca = max(1, k_b // max(m, 1))
    mask_a = _select_extreme_mask(proj_a, valid_a, m, k_a, k_a_pca)

    cap_a = selection.selection_capacity(n_a, m, alpha)
    a_sel, va_sel = selection.take_selected(a, mask_a, cap_a)
    va_sel &= jnp.any(mask_a)

    def _directed(qs, vqs, ts, vts):
        return masked_exact_hd(
            qs, ts, valid_a=vqs, valid_b=vts, directed=True,
            backend=backend, block_a=block, block_b=block,
        )

    if directed:
        hd = _directed(a_sel, va_sel, b, valid_b)
    else:
        mask_b = _select_extreme_mask(proj_b, valid_b, m, k_b, k_b_pca)
        cap_b = selection.selection_capacity(n_b, m, alpha)
        b_sel, vb_sel = selection.take_selected(b, mask_b, cap_b)
        vb_sel &= jnp.any(mask_b)
        # Full-inner mode (queries-from-subset vs full set): never
        # overestimates, so hd is itself a certified lower bound.
        hd = jnp.maximum(
            _directed(a_sel, va_sel, b, valid_b),
            _directed(b_sel, vb_sel, a, valid_a),
        )

    lower = masked_projected_hd(proj_a, valid_a, proj_b, valid_b, directed=directed)
    upper = lower + masked_additive_bound(a, proj_a, valid_a, b, proj_b, valid_b)
    return MaskedCertificate(hd=hd, lower=lower, upper=upper)


# jit entry point for one-off (non-vmapped) callers; the cascade wraps its
# own vmapped version per storage bucket.
masked_prohd_certified_jit = functools.partial(
    jax.jit, static_argnames=("alpha", "m", "directed", "block", "backend")
)(masked_prohd_certified)
