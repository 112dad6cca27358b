"""Retrieval scoring: 1 query vs 10⁶ candidates — batched dot + distributed
top-k, NOT a loop (assignment note).

This is structurally the same computation as ProHD's ANN phase (blocked
query-vs-database scan; DESIGN.md §4), so the same decomposition is used:
candidates row-sharded over the batch axes, local top-k per shard, gathered
(P, k) re-top-k — identical to repro.core.distributed's threshold selection.

Scoring modes: "dot" (two-tower / BERT4Rec / BST / FM) and "l2" (nearest-
neighbour retrieval; uses the Pallas hausdorff kernel's min-distance form).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.sharding.axes import MeshRules, current_rules


class TopK(NamedTuple):
    scores: jnp.ndarray  # (B, k)
    ids: jnp.ndarray     # (B, k) int32 — candidate row indices


def retrieval_topk(
    candidates: jnp.ndarray,   # (N, D) — row-sharded over `shard_axes`
    queries: jnp.ndarray,      # (B, D) — replicated
    k: int,
    *,
    metric: str = "dot",
    rules: MeshRules | None = None,
    shard_axes: tuple[str, ...] | None = None,
) -> TopK:
    """Distributed brute-force top-k.

    ``shard_axes`` picks which mesh axes the candidate rows live on.  §Perf:
    passing the axes the table is ALREADY sharded on (("model",) for recsys
    embedding tables) skips the model→batch reshard entirely.
    """
    rules = rules or current_rules()

    def local_scores(cand, q):
        if metric == "dot":
            return jnp.einsum("bd,nd->bn", q, cand, preferred_element_type=jnp.float32)
        if metric == "l2":
            q2 = jnp.sum(q.astype(jnp.float32) ** 2, -1, keepdims=True)
            c2 = jnp.sum(cand.astype(jnp.float32) ** 2, -1)
            d2 = q2 - 2.0 * jnp.einsum("bd,nd->bn", q, cand, preferred_element_type=jnp.float32) + c2[None]
            return -jnp.maximum(d2, 0.0)  # negative distance → top-k = nearest
        raise ValueError(metric)

    if shard_axes is None:
        shard_axes = rules.batch
    if not shard_axes or rules.mesh is None:
        s = local_scores(candidates, queries)
        vals, idx = jax.lax.top_k(s, k)
        return TopK(vals, idx.astype(jnp.int32))

    axes = tuple(shard_axes)
    mesh = rules.mesh
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    n_local = candidates.shape[0] // n_shards

    def fn(cand_local, q):
        s = local_scores(cand_local, q)                       # (B, N/P)
        k_loc = min(k, s.shape[1])
        vals, idx = jax.lax.top_k(s, k_loc)                   # (B, k)
        shard_id = jax.lax.axis_index(axes)
        gids = (idx + shard_id * n_local).astype(jnp.int32)
        if k_loc < k:
            vals = jnp.pad(vals, ((0, 0), (0, k - k_loc)), constant_values=-jnp.inf)
            gids = jnp.pad(gids, ((0, 0), (0, k - k_loc)), constant_values=-1)
        g_vals = jax.lax.all_gather(vals, axes, axis=1, tiled=True)  # (B, P*k)
        g_ids = jax.lax.all_gather(gids, axes, axis=1, tiled=True)
        top_vals, top_pos = jax.lax.top_k(g_vals, k)
        top_ids = jnp.take_along_axis(g_ids, top_pos, axis=1)
        return top_vals, top_ids

    out = shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axes, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(candidates, queries)
    return TopK(*out)
