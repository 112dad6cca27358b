"""Sparse embedding substrate for recsys: sharded lookup + EmbeddingBag.

JAX has no ``nn.EmbeddingBag`` and no CSR sparse — this module builds both
from ``jnp.take`` + ``jax.ops.segment_sum`` (the assignment calls this out
as part of the system).

Distribution: tables are ROW-sharded over the "model" axis (classic recsys
model parallelism — the tables are the only tensors that don't fit
replicated).  ``sharded_lookup`` does the lookup with an explicit
shard_map: each model shard resolves the ids it owns (masked local take)
and a psum assembles full embeddings — one (batch, dim)-sized all-reduce,
never an all-gather of the table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.sharding.axes import MeshRules, current_rules

__all__ = ["lookup", "embedding_bag", "sharded_lookup"]


def lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Plain gather — used when no mesh rules are active (tests/CPU)."""
    return jnp.take(table, ids, axis=0)


def embedding_bag(
    table: jnp.ndarray,
    flat_ids: jnp.ndarray,
    segment_ids: jnp.ndarray,
    num_segments: int,
    *,
    combiner: str = "sum",
) -> jnp.ndarray:
    """torch.nn.EmbeddingBag equivalent: gather rows, segment-reduce.

    flat_ids: (T,) indices into table; segment_ids: (T,) bag index per id
    (monotone not required).  Returns (num_segments, D).
    """
    emb = lookup(table, flat_ids)
    summed = jax.ops.segment_sum(emb, segment_ids, num_segments=num_segments)
    if combiner == "sum":
        return summed
    if combiner == "mean":
        counts = jax.ops.segment_sum(
            jnp.ones_like(flat_ids, dtype=emb.dtype), segment_ids, num_segments=num_segments
        )
        return summed / jnp.maximum(counts[:, None], 1.0)
    if combiner == "max":
        return jax.ops.segment_max(emb, segment_ids, num_segments=num_segments)
    raise ValueError(f"unknown combiner {combiner!r}")


def sharded_lookup(table: jnp.ndarray, ids: jnp.ndarray, rules: MeshRules | None = None) -> jnp.ndarray:
    """Row-sharded table lookup: masked local take + psum over "model".

    table: (V, D) sharded P("model", None); ids: any int shape, sharded over
    the batch axes (replicated over "model").  Returns (*ids.shape, D)
    embeddings, batch-sharded / model-replicated.
    """
    rules = rules or current_rules()
    if rules.model is None or rules.mesh is None:
        return lookup(table, ids)
    mesh = rules.mesh
    n_shards = mesh.shape[rules.model]
    if table.shape[0] % n_shards != 0:
        return lookup(table, ids)  # non-divisible vocab: let GSPMD decide

    batch_spec = rules.batch if rules.batch else None
    if batch_spec is not None:
        bsz = 1
        for ax in rules.batch:
            bsz *= mesh.shape[ax]
        if ids.shape[0] % bsz != 0:
            batch_spec = None  # tiny/replicated query batches (retrieval)

    def fn(tbl_local, ids_local):
        rows = tbl_local.shape[0]
        my = jax.lax.axis_index(rules.model)
        lo = my * rows
        loc = ids_local - lo
        ok = (loc >= 0) & (loc < rows)
        emb = jnp.take(tbl_local, jnp.clip(loc, 0, rows - 1), axis=0)
        emb = jnp.where(ok[..., None], emb, 0)
        return jax.lax.psum(emb, rules.model)

    out_spec = P(*([batch_spec] + [None] * (ids.ndim - 1) + [None]))
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(rules.model, None), P(*([batch_spec] + [None] * (ids.ndim - 1)))),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids)
