"""Distributed ProHD via shard_map — the paper's §Parallelism on a TPU mesh.

The paper parallelises every phase over P CPU threads; here P = the mesh's
batch-like axes (("pod","data") on the production mesh).  Point clouds are
row-sharded; per-shard validity masks make padding explicit.

Phase → collective map (see DESIGN.md §5):

  centroids        local masked sum            → psum          (2·D floats)
  PCA              local centered Gram (D×D)   → psum          (D² floats)
                   eigh replicated per shard (deterministic)
  selection        local top-k per direction   → all_gather of (P,k) values
                   global threshold → local membership masks
  subset HD        all_gather of selected pts (O(α n √D) rows) → every shard
                   scans its LOCAL db rows → pmin over shards → max
  exact HD (ring)  db shards rotate via ppermute, running min — the exact
                   "ANN-Exact" baseline at O(n²D/P) compute, O(n·D) comm

Guarantees carry over: threshold selection picks a *superset* of the exact
global top-k under ties, and queries-vs-full never overestimates, so the
distributed estimate equals the single-device estimate up to fp reduction
order (tested in tests/test_distributed.py on an 8-device host mesh).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core.exact import CERTIFIED_PRECISION
from repro.core.prohd import ProHDConfig
from repro.core import projections, selection as sel_mod

__all__ = ["distributed_prohd", "distributed_exact_hd", "ShardedCloud"]

_NEG = float("-inf")
_POS = float("inf")


class ShardedCloud(NamedTuple):
    """Row-sharded point cloud + validity mask (True = real row)."""

    points: jnp.ndarray  # (n, D), sharded over batch axes
    valid: jnp.ndarray   # (n,) bool, sharded the same way


def _masked_centroid(pts, valid, axes):
    p32 = pts.astype(jnp.float32) * valid[:, None]
    total = jax.lax.psum(jnp.sum(p32, axis=0), axes)
    count = jax.lax.psum(jnp.sum(valid.astype(jnp.float32)), axes)
    return total / jnp.maximum(count, 1.0), count


def _global_gram_directions(a, va, b, vb, m, axes):
    """Centroid direction + top-m eigenvectors of the global centered Gram."""
    ca, _ = _masked_centroid(a, va, axes)
    cb, _ = _masked_centroid(b, vb, axes)
    u0 = cb - ca
    norm = jnp.linalg.norm(u0)
    e1 = jnp.zeros_like(u0).at[0].set(1.0)
    u0 = jnp.where(norm < 1e-9, e1, u0 / jnp.maximum(norm, 1e-9))

    z = jnp.concatenate([a, b], axis=0).astype(jnp.float32)
    vz = jnp.concatenate([va, vb], axis=0)
    mean, _ = _masked_centroid(z, vz, axes)
    zc = jnp.where(vz[:, None], z - mean, 0.0)
    gram = jax.lax.psum(
        jnp.matmul(zc.T, zc, preferred_element_type=jnp.float32), axes
    )
    w, v = jnp.linalg.eigh(gram)
    us = v[:, ::-1][:, :m]  # (D, m)
    return jnp.concatenate([u0[:, None], us], axis=1)  # (D, m+1)


def _global_threshold_topk(vals, k, axes):
    """k-th largest value of ``vals`` across all shards (vals: (n_local,))."""
    k_local = min(k, vals.shape[0])
    local_top, _ = jax.lax.top_k(vals, k_local)
    if k_local < k:
        local_top = jnp.pad(local_top, (0, k - k_local), constant_values=_NEG)
    gathered = jax.lax.all_gather(local_top, axes)  # (P..., k)
    glob, _ = jax.lax.top_k(gathered.reshape(-1), k)
    return glob[k - 1]


def _select_local_mask(projs, valid, n_global, alpha, alpha_pca, axes):
    """Local membership mask for the global α-extremes, per Alg. 1/2/3."""
    m = projs.shape[1] - 1
    mask = jnp.zeros(projs.shape[:1], jnp.bool_)
    for col in range(projs.shape[1]):
        frac = alpha if col == 0 else alpha_pca
        k = sel_mod.alpha_count(n_global, frac)
        p = projs[:, col]
        hi = _global_threshold_topk(jnp.where(valid, p, _NEG), k, axes)
        lo = -_global_threshold_topk(jnp.where(valid, -p, _NEG), k, axes)
        mask = mask | (valid & ((p >= hi) | (p <= lo)))
    return mask


def _gather_selected(points, mask, capacity, axes):
    """Pack local selected rows to a padded buffer, all_gather across shards."""
    pts, valid = sel_mod.take_selected(points, mask, capacity)
    # A shard with zero selected rows would pack garbage row 0 — valid=False
    # keeps it out of every downstream min/max.
    g_pts = jax.lax.all_gather(pts, axes, tiled=True)       # (P*cap, D)
    g_valid = jax.lax.all_gather(valid & mask.any(), axes, tiled=True)
    return g_pts, g_valid


def _queries_vs_sharded_db(queries, q_valid, db, db_valid, axes, block=2048):
    """max_{q valid} min over ALL db shards of ||q - db||; psum-free via pmin."""
    from repro.core import exact

    n_q = queries.shape[0]
    db_masked_valid = db_valid
    # Local per-query min distance (squared) against this shard's db rows.
    a32 = queries.astype(jnp.float32)
    d32 = db.astype(jnp.float32)
    a2 = jnp.sum(a32 * a32, axis=1, keepdims=True)
    d2n = jnp.sum(d32 * d32, axis=1)
    d2 = a2 - 2.0 * jnp.matmul(
        a32, d32.T, precision=CERTIFIED_PRECISION, preferred_element_type=jnp.float32
    ) + d2n[None, :]
    d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(db_masked_valid[None, :], d2, _POS)
    local_min = jnp.min(d2, axis=1)                        # (n_q,)
    global_min = jax.lax.pmin(local_min, axes)             # (n_q,) replicated
    global_min = jnp.where(q_valid, global_min, _NEG)
    return jnp.sqrt(jnp.max(global_min))


def distributed_prohd(
    mesh: jax.sharding.Mesh,
    a: ShardedCloud,
    b: ShardedCloud,
    cfg: ProHDConfig = ProHDConfig(),
    *,
    batch_axes: Sequence[str] = ("data",),
):
    """Multi-device ProHD.  a/b.points must be sharded over ``batch_axes``.

    Returns (hd, n_sel_a, n_sel_b) replicated scalars.  Uses the certified
    queries-vs-full inner mode (ProHDConfig.inner is honoured: "subset" uses
    the gathered subset as the database instead).
    """
    axes = tuple(batch_axes)
    n_a = a.points.shape[0]
    n_b = b.points.shape[0]
    d = a.points.shape[1]
    m = cfg.resolve_m(d)
    alpha_pca = cfg.alpha_pca if cfg.alpha_pca is not None else cfg.alpha / max(1, m)
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    cap_a = min(n_a // n_shards, sel_mod.selection_capacity(n_a, m, cfg.alpha, alpha_pca))
    cap_b = min(n_b // n_shards, sel_mod.selection_capacity(n_b, m, cfg.alpha, alpha_pca))

    def shard_fn(ap, av, bp, bv):
        dirs = _global_gram_directions(ap, av, bp, bv, m, axes)
        proj_a = projections.project(ap, dirs)
        proj_b = projections.project(bp, dirs)
        mask_a = _select_local_mask(proj_a, av, n_a, cfg.alpha, alpha_pca, axes)
        mask_b = _select_local_mask(proj_b, bv, n_b, cfg.alpha, alpha_pca, axes)

        qa, qa_valid = _gather_selected(ap, mask_a, cap_a, axes)
        qb, qb_valid = _gather_selected(bp, mask_b, cap_b, axes)

        if cfg.inner == "full":
            h_ab = _queries_vs_sharded_db(qa, qa_valid, bp, bv, axes)
            h_ba = _queries_vs_sharded_db(qb, qb_valid, ap, av, axes)
        else:  # literal Alg. 3: subset vs subset (both replicated post-gather)
            from repro.core import exact

            h_ab = exact.directed_hd_tiled(qa, qb, valid_a=qa_valid, valid_b=qb_valid)
            h_ba = exact.directed_hd_tiled(qb, qa, valid_a=qb_valid, valid_b=qa_valid)

        hd = jnp.maximum(h_ab, h_ba)
        n_sel_a = jax.lax.psum(jnp.sum(mask_a.astype(jnp.int32)), axes)
        n_sel_b = jax.lax.psum(jnp.sum(mask_b.astype(jnp.int32)), axes)
        return hd, n_sel_a, n_sel_b

    spec_pts = P(axes, None)
    spec_row = P(axes)
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec_pts, spec_row, spec_pts, spec_row),
        out_specs=(P(), P(), P()),
        check_vma=False,  # outputs derive from psum/pmin/all_gather → replicated
    )
    return fn(a.points, a.valid, b.points, b.valid)


def distributed_exact_hd(
    mesh: jax.sharding.Mesh,
    a: ShardedCloud,
    b: ShardedCloud,
    *,
    batch_axes: Sequence[str] = ("data",),
):
    """Exact H(A,B) with both clouds row-sharded: ring algorithm.

    Each of P steps, every shard holds a rotating block of the database and
    folds it into the running per-query min via a local GEMM; ppermute moves
    blocks around the ring so peak memory stays O(n/P · D) and the GEMM of
    step i overlaps the transfer of step i+1.
    """
    axes = tuple(batch_axes)
    sizes = [mesh.shape[ax] for ax in axes]
    n_shards = 1
    for s in sizes:
        n_shards *= s

    def ring_min(qp, qv, dbp, dbv):
        """Per-local-query min distance over the FULL db via ring rotation."""
        q32 = qp.astype(jnp.float32)
        q2 = jnp.sum(q32 * q32, axis=1, keepdims=True)

        def step(carry, _):
            mins, blk, blk_valid = carry
            b32 = blk.astype(jnp.float32)
            b2 = jnp.sum(b32 * b32, axis=1)
            d2 = q2 - 2.0 * jnp.matmul(
                q32, b32.T, precision=CERTIFIED_PRECISION,
                preferred_element_type=jnp.float32,
            ) + b2[None, :]
            d2 = jnp.maximum(d2, 0.0)
            d2 = jnp.where(blk_valid[None, :], d2, _POS)
            mins = jnp.minimum(mins, jnp.min(d2, axis=1))
            # rotate db block to the next shard in the flattened ring
            perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
            blk = jax.lax.ppermute(blk, axes, perm)
            blk_valid = jax.lax.ppermute(blk_valid, axes, perm)
            return (mins, blk, blk_valid), None

        mins0 = jnp.full((qp.shape[0],), _POS, jnp.float32)
        (mins, _, _), _ = jax.lax.scan(step, (mins0, dbp, dbv), None, length=n_shards)
        mins = jnp.where(qv, mins, _NEG)
        return jax.lax.pmax(jnp.max(mins), axes)

    def shard_fn(ap, av, bp, bv):
        h_ab = ring_min(ap, av, bp, bv)
        h_ba = ring_min(bp, bv, ap, av)
        return jnp.sqrt(jnp.maximum(h_ab, h_ba))

    spec_pts = P(axes, None)
    spec_row = P(axes)
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec_pts, spec_row, spec_pts, spec_row),
        out_specs=P(),
        check_vma=False,  # pmax output is replicated
    )
    return fn(a.points, a.valid, b.points, b.valid)
