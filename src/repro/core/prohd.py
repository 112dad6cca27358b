"""ProHD main procedure (paper Alg. 3) as a composable, jit-able JAX module.

Public API:

    cfg = ProHDConfig(alpha=0.01)
    est = prohd(a, b, cfg, key=key)          # ProHDEstimate

``prohd`` is fully jittable for fixed shapes/config (all data-dependent sizes
are padded to static capacities derived from (n, D, alpha)).  The subset HD
backend is pluggable: "tiled" (pure-JAX GEMM scan — default, runs anywhere)
or "pallas" (the repro.kernels.hausdorff TPU kernel).

Paper ↔ code map:
    Alg. 1 CentroidIndices   → projections.centroid_direction + selection.extreme_mask
    Alg. 2 PCAProjIndices    → projections.pca_directions + selection.extreme_mask_multi
    Alg. 3 ProjHausdorff     → prohd() below
    Eq. (4)/(5) bound        → bounds.additive_bound (returned in the estimate)

Faithfulness note (full analysis in DESIGN.md §7): the paper's pseudocode,
theory and experiments are mutually inconsistent about what the final ANN
step searches over.  Alg. 3 as typeset computes HD *subset-vs-subset*, but
§II-E.5 ("never overestimates"), Table II subset sizes, and the reported
errors/runtimes are only consistent with *queries-from-subset vs full-set*
nearest-neighbour search (h(A_sel → B), a certified underestimate).  We
implement both (``ProHDConfig.inner``), defaulting to the reading that
matches the paper's claims and numbers ("full").
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bounds, exact, projected, projections, selection, tile_bounds

__all__ = ["SCOPES", "ProHDConfig", "ProHDEstimate", "prohd", "prohd_masks"]

# The ``jax.named_scope`` of each phase of Alg. 3 inside ``prohd``: the
# selection (directions, projections, extreme masks, gathers), the two
# nearest-neighbour sweeps, the additive bound and the certified lower
# bound.  They change only the ops' ``op_name`` metadata, never the
# program; ``repro.obs.xla.op_scopes`` maps a profile's device ops to them.
SCOPES = ("prohd.select", "prohd.nn_scan", "prohd.bound", "prohd.lower_bound")
SELECT, NN_SCAN, BOUND, LOWER_BOUND = SCOPES

SubsetBackend = Literal["tiled", "dense", "pallas"]


@dataclasses.dataclass(frozen=True)
class ProHDConfig:
    """Runtime knobs.  ``alpha`` is the paper's selection fraction; everything
    else defaults to the paper's choices."""

    alpha: float = 0.01
    # m = None → paper default floor(sqrt(D)).
    num_pca_directions: int | None = None
    # α' = alpha_pca; None → paper default alpha / m.
    alpha_pca: float | None = None
    pca_method: projections.PCAMethod = "gram"
    subset_backend: SubsetBackend = "tiled"
    subset_block: int = 2048
    # Inner-min candidate set for the final HD (see module docstring):
    #   "full"   — queries from the selected subsets, nearest-neighbour search
    #              against the FULL other cloud.  Certified underestimate
    #              (max over a subset of true min-distances); this is the only
    #              reading consistent with the paper's §II-E.5 "never
    #              overestimates" theorem, its Table II subset sizes and its
    #              reported runtimes/errors.  Default.
    #   "subset" — Alg. 3 exactly as typeset (index built on the subset too).
    #              Cheaper, but the restricted inner min CAN overestimate
    #              (measured +11% on 100k uniform clouds at D=8).
    inner: Literal["full", "subset"] = "full"
    # Projection pruning inside the distance scan (PR 1): reorder each cloud
    # along the primary projection and hand the scan per-tile lower bounds +
    # witness cutoffs (repro.core.tile_bounds), so tiles that provably cannot
    # contain any min skip their GEMM.  Exactness is unaffected (tested);
    # effectiveness depends on how separated the clouds are along the
    # projections — the very signal ProHD selects on.
    prune: bool = False
    compute_bound: bool = True
    # Also compute the certified projected estimator max_u H_u (see
    # repro.core.projected for why this differs from the subset estimator).
    compute_projected: bool = True

    def resolve_m(self, d: int) -> int:
        return self.num_pca_directions if self.num_pca_directions is not None else projections.default_num_directions(d)


class ProHDEstimate(NamedTuple):
    """What Alg. 3 returns, plus the §II-E certificate.

    ``hd`` is the paper-faithful subset estimator (Alg. 3 line 6-7); it is
    usually the better point estimate but carries no one-sided guarantee.
    ``hd_proj`` is max_u H_u(A,B) — the estimator the paper's theory bounds:
        hd_proj ≤ H(A,B) ≤ hd_proj + bound.
    """

    hd: jnp.ndarray          # Ĥ(A,B) scalar fp32 (subset estimator)
    n_sel_a: jnp.ndarray     # |I^A| (int32)
    n_sel_b: jnp.ndarray     # |I^B|
    bound: jnp.ndarray       # 2·min_u δ(u); 0 if compute_bound=False
    hd_proj: jnp.ndarray     # certified lower bound; 0 if compute_projected=False


def _directed(a, b, va, vb, cfg: ProHDConfig, prune_projs=None) -> jnp.ndarray:
    """One directed sweep h(a → b) on the configured backend.

    Each sweep runs on the fused-scan machinery (hoisted norms; optional
    projection pruning).  The two sweeps of the "full" inner mode scan
    DIFFERENT products (A_sel × B_full and B_sel × A_full, ~2αn² total), so
    bidirectionally fusing them would mean one full n² pass — strictly more
    FLOPs; they stay separate by design.
    """
    if cfg.subset_backend == "dense":
        return exact.directed_hd_dense(a, b, valid_a=va, valid_b=vb)
    if cfg.subset_backend == "pallas":
        from repro.kernels.hausdorff import ops as hd_ops

        return hd_ops.directed_hausdorff(
            a, b, valid_a=va, valid_b=vb, prune_projs=prune_projs
        )
    return exact.directed_hd_tiled(
        a, b, valid_a=va, valid_b=vb, block=cfg.subset_block, prune_projs=prune_projs
    )


def _queries_vs_full_hd(
    a_sel, va, b_sel, vb, a_full, b_full, cfg: ProHDConfig, projs=None
) -> jnp.ndarray:
    """h = max( h(A_sel → B_full), h(B_sel → A_full) ) — certified ≤ H(A,B)."""
    pab = pba = None
    if projs is not None:
        proj_a_sel, proj_b_sel, proj_a_full, proj_b_full = projs
        pab = (proj_a_sel, proj_b_full)
        pba = (proj_b_sel, proj_a_full)
    return jnp.maximum(
        _directed(a_sel, b_full, va, None, cfg, prune_projs=pab),
        _directed(b_sel, a_full, vb, None, cfg, prune_projs=pba),
    )


def _subset_hd(a_sel, va, b_sel, vb, cfg: ProHDConfig, prune_projs=None) -> jnp.ndarray:
    """Undirected H(A_sel, B_sel) in a SINGLE fused pass: the d² tiles are
    computed once and reduced in both directions (half the GEMM work of the
    historical two directed sweeps)."""
    if cfg.subset_backend == "dense":
        return exact.hausdorff_dense(a_sel, b_sel, valid_a=va, valid_b=vb)
    if cfg.subset_backend == "pallas":
        from repro.kernels.hausdorff import ops as hd_ops

        return hd_ops.hausdorff(
            a_sel, b_sel, valid_a=va, valid_b=vb, prune_projs=prune_projs
        )
    return exact.hausdorff_fused_tiled(
        a_sel,
        b_sel,
        valid_a=va,
        valid_b=vb,
        block_a=cfg.subset_block,
        block_b=cfg.subset_block,
        prune_projs=prune_projs,
    )


def prohd_masks(a, b, cfg: ProHDConfig, *, key: jax.Array | None = None) -> selection.SelectionResult:
    """Selection step only (Alg. 3 lines 1-4): masks + projections."""
    d = a.shape[1]
    m = cfg.resolve_m(d)
    dirs = projections.direction_set(a, b, m, method=cfg.pca_method, key=key)
    return selection.select_extremes(a, b, dirs, alpha=cfg.alpha, alpha_pca=cfg.alpha_pca)


@functools.partial(jax.jit, static_argnames=("cfg",))
def prohd(a: jnp.ndarray, b: jnp.ndarray, cfg: ProHDConfig = ProHDConfig(), *, key: jax.Array | None = None) -> ProHDEstimate:
    """Full ProHD (Alg. 3): select extremes, exact HD on the selected subsets.

    a: (n_a, D), b: (n_b, D).  Returns a ProHDEstimate; ``hd`` never
    overestimates the true H(A,B) (§II-E.5) and
    ``hd + bound`` never underestimates it (Eq. 5).
    """
    n_a, d = a.shape
    n_b = b.shape[0]
    m = cfg.resolve_m(d)
    if key is None and cfg.pca_method != "gram":
        raise ValueError("randomized PCA backends need key=")

    with jax.named_scope(SELECT):
        mask_a, mask_b, proj_a, proj_b = prohd_masks(a, b, cfg, key=key)

        if cfg.prune:
            # Reorder each cloud along the primary projection (HD is a set
            # metric — any consistent permutation is a no-op) so that
            # block-contiguous rows cover disjoint 1-D ranges and the tile
            # interval gaps in tile_bounds actually bite.
            a, proj_a, _, perm_a = tile_bounds.order_by_projection(a, proj_a)
            b, proj_b, _, perm_b = tile_bounds.order_by_projection(b, proj_b)
            mask_a = mask_a[perm_a]
            mask_b = mask_b[perm_b]

        cap_a = selection.selection_capacity(n_a, m, cfg.alpha, cfg.alpha_pca)
        cap_b = selection.selection_capacity(n_b, m, cfg.alpha, cfg.alpha_pca)
        a_sel, va = selection.take_selected(a, mask_a, cap_a)
        b_sel, vb = selection.take_selected(b, mask_b, cap_b)

        if cfg.prune:
            # Gathering preserves sort order, so the subsets stay
            # projection-sorted and their prune tables stay effective.
            proj_a_sel, _ = selection.take_selected(proj_a, mask_a, cap_a)
            proj_b_sel, _ = selection.take_selected(proj_b, mask_b, cap_b)

    with jax.named_scope(NN_SCAN):
        if cfg.prune:
            if cfg.inner == "full":
                hd = _queries_vs_full_hd(
                    a_sel, va, b_sel, vb, a, b, cfg,
                    projs=(proj_a_sel, proj_b_sel, proj_a, proj_b),
                )
            else:
                hd = _subset_hd(a_sel, va, b_sel, vb, cfg, prune_projs=(proj_a_sel, proj_b_sel))
        elif cfg.inner == "full":
            hd = _queries_vs_full_hd(a_sel, va, b_sel, vb, a, b, cfg)
        else:
            hd = _subset_hd(a_sel, va, b_sel, vb, cfg)

    # NB: use the (possibly permuted) locals so rows of a/proj_a stay aligned.
    if cfg.compute_bound:
        with jax.named_scope(BOUND):
            bound = bounds.additive_bound(a, b, proj_a, proj_b)
    else:
        bound = jnp.float32(0.0)

    if cfg.compute_projected:
        with jax.named_scope(LOWER_BOUND):
            hd_proj = projected.projected_hd(proj_a, proj_b)
    else:
        hd_proj = jnp.float32(0.0)

    with jax.named_scope(SELECT):
        n_sel_a = mask_a.sum().astype(jnp.int32)
        n_sel_b = mask_b.sum().astype(jnp.int32)

    return ProHDEstimate(
        hd=hd,
        n_sel_a=n_sel_a,
        n_sel_b=n_sel_b,
        bound=bound,
        hd_proj=hd_proj,
    )
