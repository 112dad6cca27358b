"""GAT (Veličković et al., arXiv:1710.10903) via edge-list message passing.

JAX has no CSR SpMM — message passing is built from first principles with
``jnp.take`` (gather) + ``jax.ops.segment_sum`` / ``segment_max`` scatter
reductions over an edge index, per the assignment.  The kernel regime is
SDDMM (edge scores) → segment-softmax → SpMM (weighted aggregation).

Sharding: edge-parallel — edge arrays and edge-indexed intermediates are
sharded over the batch axes; node tensors replicated (they are ≤ a few
hundred MB even for ogb-products).  The segment_sum over a sharded edge set
becomes local scatter-add + psum under GSPMD.

Shapes with multiple graphs (``molecule``) arrive pre-flattened as one
block-diagonal graph with ``graph_ids`` for readout — the standard batching.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from jax import shard_map

from repro.configs.base import GNNConfig
from repro.sharding.axes import MeshRules, shard


def init_gat_params(key: jax.Array, cfg: GNNConfig, in_dim: int, n_classes: int) -> dict:
    """2-layer GAT: (in → heads×hidden, ELU) → (heads·hidden → classes)."""
    ks = jax.random.split(key, 8)
    h, dh = cfg.n_heads, cfg.d_hidden
    mid = h * dh

    def glorot(k, shape):
        lim = (6.0 / (shape[0] + shape[-1])) ** 0.5
        return jax.random.uniform(k, shape, cfg.dtype, -lim, lim)

    return {
        "l1": {
            "w": glorot(ks[0], (in_dim, h, dh)),
            "a_src": glorot(ks[1], (h, dh)),
            "a_dst": glorot(ks[2], (h, dh)),
            "b": jnp.zeros((h, dh), cfg.dtype),
        },
        "l2": {
            # output layer: single averaged head over n_classes (GAT paper)
            "w": glorot(ks[3], (mid, h, n_classes)),
            "a_src": glorot(ks[4], (h, n_classes)),
            "a_dst": glorot(ks[5], (h, n_classes)),
            "b": jnp.zeros((h, n_classes), cfg.dtype),
        },
    }


def gat_param_specs(params: dict, rules: MeshRules) -> Any:
    # weights are tiny → replicated
    return jax.tree.map(lambda _: rules.spec(), params)


def _gat_layer(x, lp, src, dst, emask, n_nodes, *, negative_slope, concat_heads):
    """x: (N, F_in) → (N, H·F_out) (concat) or (N, F_out) (head-mean).

    emask: (E,) {0,1} — padded/invalid edges contribute nothing (their
    softmax logit is -inf).  Edge arrays may be padded to shard-divisible
    lengths by the input pipeline.
    """
    h = jnp.einsum("nf,fhd->nhd", x, lp["w"])          # (N, H, Dh)
    alpha_src = jnp.sum(h * lp["a_src"], axis=-1)      # (N, H)
    alpha_dst = jnp.sum(h * lp["a_dst"], axis=-1)

    # SDDMM: per-edge attention logits (edge-sharded)
    e = jnp.take(alpha_src, src, axis=0) + jnp.take(alpha_dst, dst, axis=0)
    e = jax.nn.leaky_relu(e, negative_slope)           # (E, H)
    e = jnp.where(emask[:, None] > 0, e, -1e30)
    e = shard(e, "batch", None)

    # segment-softmax over incoming edges of each dst node
    e_max = jax.ops.segment_max(e, dst, num_segments=n_nodes)       # (N, H)
    e_max = jnp.maximum(e_max, -1e29)  # nodes with no real edges
    w = jnp.exp(e - jnp.take(e_max, dst, axis=0)) * emask[:, None]
    denom = jax.ops.segment_sum(w, dst, num_segments=n_nodes)       # (N, H)
    w = w / jnp.maximum(jnp.take(denom, dst, axis=0), 1e-9)
    w = shard(w, "batch", None)

    # SpMM: weighted message aggregation
    h_src = shard(jnp.take(h, src, axis=0), "batch", None, None)    # (E, H, Dh)
    msg = h_src * w[..., None]
    msg = shard(msg, "batch", None, None)
    out = jax.ops.segment_sum(msg, dst, num_segments=n_nodes) + lp["b"]
    if concat_heads:
        return out.reshape(n_nodes, -1)
    return jnp.mean(out, axis=1)


def with_self_loops(src, dst, n_nodes, *, pad_to: int | None = None):
    """Append self-loops and (optionally) pad to a shard-divisible length.

    Returns (src, dst, mask) — the canonical preprocessing for gat_forward.
    """
    loops = jnp.arange(n_nodes, dtype=src.dtype)
    src = jnp.concatenate([src, loops])
    dst = jnp.concatenate([dst, loops])
    mask = jnp.ones(src.shape, jnp.float32)
    if pad_to is not None and pad_to > src.shape[0]:
        extra = pad_to - src.shape[0]
        src = jnp.concatenate([src, jnp.zeros((extra,), src.dtype)])
        dst = jnp.concatenate([dst, jnp.zeros((extra,), dst.dtype)])
        mask = jnp.concatenate([mask, jnp.zeros((extra,), jnp.float32)])
    return src, dst, mask


def gat_forward(params: dict, batch: dict, cfg: GNNConfig) -> jnp.ndarray:
    """batch: feats (N,F), edge_src/edge_dst (E,) int32 (self-loops included
    by the pipeline — see with_self_loops), optional edge_mask (E,)."""
    x = batch["feats"]
    n = x.shape[0]
    src = shard(batch["edge_src"], "batch")
    dst = shard(batch["edge_dst"], "batch")
    emask = batch.get("edge_mask")
    if emask is None:
        emask = jnp.ones(src.shape, jnp.float32)
    emask = shard(emask, "batch")

    h = _gat_layer(x, params["l1"], src, dst, emask, n,
                   negative_slope=cfg.negative_slope, concat_heads=True)
    h = jax.nn.elu(h)
    return _gat_layer(h, params["l2"], src, dst, emask, n,
                      negative_slope=cfg.negative_slope, concat_heads=False)


def gat_node_loss(params: dict, batch: dict, cfg: GNNConfig):
    """Node classification CE on masked (labelled) nodes."""
    logits = gat_forward(params, batch, cfg)  # (N, C)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    mask = batch["label_mask"].astype(jnp.float32)
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == batch["labels"]) * mask) / jnp.maximum(
        jnp.sum(mask), 1.0
    )
    return loss, {"ce_loss": loss, "acc": acc}


# ---------------------------------------------------------------------------
# §Perf variant: dst-owner node partitioning (no node-field psums)
# ---------------------------------------------------------------------------
#
# Baseline edge-parallel GAT pays 2 segment-reductions per layer that each
# end in a full (N, H·Dh) all-reduce (every shard scatters into every node).
# The partitioned variant assigns each node to one shard (its "owner") and
# requires the input pipeline to route every edge to its DST's owner
# (standard graph partitioning).  Then all segment reductions are LOCAL;
# the only collective is one all-gather of the (N, H, Dh) projected
# features per layer so shards can read remote SRC rows.


def gat_forward_partitioned(
    params: dict, batch: dict, cfg: GNNConfig, rules, *, gather_dtype=None
) -> jnp.ndarray:
    """Node-partitioned GAT via shard_map.

    Contract: nodes are owner-ordered (shard i owns the contiguous block
    [i·N/P, (i+1)·N/P)); edge arrays are grouped so shard i's slice only
    contains edges whose dst lies in its block (the synthetic dry-run
    specs satisfy this trivially; data/graphs.py's partitioner does it for
    real graphs).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    axes = rules.batch
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    n = batch["feats"].shape[0]
    n_local = n // n_shards

    def shard_fn(feats_local, src, dst, emask, l1, l2):
        shard_id = jax.lax.axis_index(axes)
        base = shard_id * n_local

        def layer(x_local, lp, out_dim, concat):
            # local projection, then one all-gather so src gathers see all nodes
            h_local = jnp.einsum("nf,fhd->nhd", x_local, lp["w"])
            a_src_local = jnp.sum(h_local * lp["a_src"], axis=-1)
            # §Perf iteration 2: gather in bf16 — halves the only collective
            g_dtype = gather_dtype or h_local.dtype
            h_full = jax.lax.all_gather(h_local.astype(g_dtype), axes, tiled=True).astype(h_local.dtype)
            a_src_full = jax.lax.all_gather(a_src_local.astype(g_dtype), axes, tiled=True).astype(h_local.dtype)
            a_dst_local = jnp.sum(h_local * lp["a_dst"], axis=-1)        # (n_local, H)

            dst_local = dst - base                                        # owner-local ids
            e = jnp.take(a_src_full, src, axis=0) + jnp.take(a_dst_local, dst_local, axis=0)
            e = jax.nn.leaky_relu(e, cfg.negative_slope)
            e = jnp.where(emask[:, None] > 0, e, -1e30)
            e_max = jax.ops.segment_max(e, dst_local, num_segments=n_local)
            e_max = jnp.maximum(e_max, -1e29)
            w = jnp.exp(e - jnp.take(e_max, dst_local, axis=0)) * emask[:, None]
            denom = jax.ops.segment_sum(w, dst_local, num_segments=n_local)
            w = w / jnp.maximum(jnp.take(denom, dst_local, axis=0), 1e-9)
            msg = jnp.take(h_full, src, axis=0) * w[..., None]
            out = jax.ops.segment_sum(msg, dst_local, num_segments=n_local) + lp["b"]
            if concat:
                return out.reshape(n_local, -1)
            return jnp.mean(out, axis=1)

        h = jax.nn.elu(layer(feats_local, l1, cfg.d_hidden, True))
        return layer(h, l2, None, False)                                  # (n_local, C)

    spec_nodes = P(axes, None)
    spec_edges = P(axes)
    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec_nodes, spec_edges, spec_edges, spec_edges,
                  jax.tree.map(lambda _: P(), params["l1"]),
                  jax.tree.map(lambda _: P(), params["l2"])),
        out_specs=P(axes, None),
        check_vma=False,
    )
    return fn(batch["feats"], batch["edge_src"], batch["edge_dst"],
              batch.get("edge_mask", jnp.ones(batch["edge_src"].shape, jnp.float32)),
              params["l1"], params["l2"])


def gat_node_loss_partitioned(params: dict, batch: dict, cfg: GNNConfig, rules, gather_dtype=None):
    logits = gat_forward_partitioned(params, batch, cfg, rules, gather_dtype=gather_dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(batch["labels"], logits.shape[-1], dtype=logp.dtype)
    ll = jnp.einsum("nc,nc->n", logp, onehot)
    mask = batch["label_mask"].astype(jnp.float32)
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == batch["labels"]) * mask) / jnp.maximum(
        jnp.sum(mask), 1.0
    )
    return loss, {"ce_loss": loss, "acc": acc}


def gat_graph_loss(params: dict, batch: dict, cfg: GNNConfig):
    """Graph classification: mean-readout per graph_id then CE (molecule)."""
    node_out = gat_forward(params, batch, cfg)  # (N, C)
    gids = batch["graph_ids"]
    n_graphs = batch["labels"].shape[0]
    summed = jax.ops.segment_sum(node_out, gids, num_segments=n_graphs)
    counts = jax.ops.segment_sum(jnp.ones((node_out.shape[0], 1)), gids, num_segments=n_graphs)
    logits = (summed / jnp.maximum(counts, 1.0)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    loss = -jnp.mean(ll)
    return loss, {"ce_loss": loss}
