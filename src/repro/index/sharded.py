"""Corpus-parallel stage 0 / stage 1 via ``shard_map``.

``search(..., shards=P)`` / ``search_batch(..., shards=P)`` partition the
cascade's two bucket-granularity passes across a 1-D device mesh over the
``"corpus"`` axis (the same axis discipline as ``repro.core.distributed``
and ``repro.sharding``):

  stage 0 — the (N,)-stacked summaries are split row-wise across shards;
      each shard runs the SAME :func:`repro.index.cascade.interval_bounds`
      / ``bound_scale`` math on its local partition and the gathered
      result is the full (N,) bound vector.  Every bound is row-local
      arithmetic (no cross-row reduction), so the per-row bits are
      UNCHANGED by how rows are split — sharding stage 0 is a pure layout
      transform.
  stage 1 — a surviving bucket's frontier lanes are assigned to shards
      round-robin by slot; each shard vmaps the masked ProHD certificate
      (:func:`repro.core.masked.masked_prohd_certified`) over its local
      lanes of the slab and the host scatters the gathered certificates
      back into frontier order.
  merge — the per-shard certificates land in the SAME (lb, ub) interval
      state, and :func:`merge_topk` re-applies the global prune rule
      ``lb > k-th smallest certified ub`` over the full corpus — the
      cross-shard certified top-k merge.  The unchanged stage-2 raw
      refinement then drains the merged frontier.

Why the sharded top-k is bit-for-bit the single-device result: the
cascade's returned values ALWAYS come from stage-2 raw refines on the
unpadded points (identical bits by construction), and its membership is
provably the brute-force top-k under ANY certified bounds — stages 0/1
only ever decide how much work stage 2 does.  Sharding can therefore not
perturb a bit of the output even where per-lane stage-1 GEMM bits shift
with the local batch shape (they may: fp32 GEMM bits are not invariant
across shapes — see the conformance notes); the identity is certified by
the sharded-vs-single-device gate in ``scripts/check.sh`` under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

``shards=1`` builds a one-device mesh and exercises this exact code path
without multi-device XLA flags — how the tier-1 suite covers it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import masked
from repro.index import cascade as _cascade
from repro.index.store import bucket_capacity
from jax import shard_map

__all__ = [
    "ShardContext",
    "make_shard_context",
    "stage0_bounds",
    "stage0_multiquery",
    "stage1_certs",
    "merge_topk",
]


class ShardContext:
    """One corpus mesh + the jitted shard_map calls compiled against it.

    Created per search call (cheap: the mesh is a view over existing
    devices; compiled executables are cached by jax on (fn, shapes), and
    the per-context dicts keep one traced wrapper per static-arg key so
    repeated buckets/hyperparameters reuse the trace).
    """

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.n_shards = int(np.prod(list(mesh.shape.values())))
        self._stage0: dict = {}
        self._stage0_multi: dict = {}
        self._stage1: dict = {}


def make_shard_context(shards: int) -> ShardContext:
    """A :class:`ShardContext` over the first ``shards`` visible devices."""
    shards = int(shards)
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    devices = jax.devices()
    if shards > len(devices):
        raise ValueError(
            f"shards={shards} exceeds the {len(devices)} visible "
            f"device(s); force host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N or "
            f"lower shards"
        )
    mesh = Mesh(np.asarray(devices[:shards]), axis_names=("corpus",))
    return ShardContext(mesh)


def _pad_summaries(ssums, n: int, p: int):
    """Pad every (N, ...) summary field to a multiple of ``p`` rows by
    repeating row 0 (the stage-0 math is row-local, so pad rows cannot
    perturb real rows; callers slice results back to ``n``)."""
    pad = (-n) % p
    if pad == 0:
        return ssums, n

    def _pad(leaf):
        return jnp.concatenate([leaf, jnp.repeat(leaf[:1], pad, axis=0)], axis=0)

    return jax.tree_util.tree_map(_pad, ssums), n + pad


def stage0_bounds(ctx: ShardContext, qsum, ssums, *, directed: bool):
    """Sharded single-query stage 0: raw certified (lb, ub, scale), each
    (N,) float64 numpy — the corpus rows split across ``ctx``'s mesh, the
    query summary replicated.  Same RAW bounds contract as the in-process
    path: callers apply ``certified_margins`` before pruning."""
    n = int(np.asarray(ssums.count).shape[0])
    padded, _ = _pad_summaries(ssums, n, ctx.n_shards)
    fn = ctx._stage0.get(directed)
    if fn is None:
        def _local(qs, ss):
            lo, hi = _cascade.interval_bounds(qs, ss, directed=directed)
            return lo, hi, _cascade.bound_scale(qs, ss)

        fn = jax.jit(shard_map(
            _local, mesh=ctx.mesh,
            in_specs=(P(), P("corpus")), out_specs=P("corpus"),
            check_vma=False,
        ))
        ctx._stage0[directed] = fn
    lo, hi, scale = fn(qsum, padded)
    return (
        np.asarray(lo, np.float64)[:n],
        np.asarray(hi, np.float64)[:n],
        np.asarray(scale, np.float64)[:n],
    )


def stage0_multiquery(ctx: ShardContext, qsums, ssums, *, directed: bool):
    """Sharded batch stage 0: raw certified (lb, ub, scale), each (Q, N)
    float64 numpy.  ``qsums`` carries the broadcast axis ((Q, 1, ...) per
    field, replicated on every shard) exactly as in
    ``multiquery._stage0_multiquery``; the corpus axis is sharded."""
    n = int(np.asarray(ssums.count).shape[0])
    padded, _ = _pad_summaries(ssums, n, ctx.n_shards)
    fn = ctx._stage0_multi.get(directed)
    if fn is None:
        def _local(qs, ss):
            lo, hi = _cascade.interval_bounds(qs, ss, directed=directed)
            return lo, hi, _cascade.bound_scale(qs, ss)

        fn = jax.jit(shard_map(
            _local, mesh=ctx.mesh,
            in_specs=(P(), P("corpus")), out_specs=P(None, "corpus"),
            check_vma=False,
        ))
        ctx._stage0_multi[directed] = fn
    lo, hi, scale = fn(qsums, padded)
    return (
        np.asarray(lo, np.float64)[:, :n],
        np.asarray(hi, np.float64)[:, :n],
        np.asarray(scale, np.float64)[:, :n],
    )


def stage1_certs(
    ctx: ShardContext, q, bucket, rows: np.ndarray, *,
    alpha: float, m: int, directed: bool, backend: str,
):
    """Sharded stage 1 for one bucket: masked ProHD certificates of the
    frontier ``rows``, lanes assigned to shards round-robin by slot.

    Returns a :class:`repro.core.masked.MaskedCertificate` of numpy
    arrays in FRONTIER ORDER, already sliced to ``rows.size`` (unlike the
    in-process ``_stage1_batch``, whose padded tail the caller slices).
    Lane padding repeats row 0 — the same jit-cache discipline as
    ``_pow2_take`` — then rounds up to a multiple of the shard count so
    every shard holds the same lane count.
    """
    p = ctx.n_shards
    lanes = int(rows.size)
    width = max(bucket_capacity(lanes, 1), p)
    width = ((width + p - 1) // p) * p
    pad_rows = np.concatenate([rows, np.full((width - lanes,), rows[0])])
    # Round-robin by slot: permuted position j on shard s covers original
    # lane s + j·P — the (capacity, slot) striping the docs promise.
    order = np.concatenate([np.arange(s, width, p) for s in range(p)])
    inv = np.empty((width,), np.int64)
    inv[order] = np.arange(width)
    take = jnp.asarray(pad_rows[order])

    key = (float(alpha), int(m), bool(directed), str(backend))
    fn = ctx._stage1.get(key)
    if fn is None:
        def _local(qq, pts, valid):
            va = jnp.ones((qq.shape[0],), jnp.bool_)

            def one(pp, vv):
                return masked.masked_prohd_certified(
                    qq, va, pp, vv,
                    alpha=alpha, m=m, directed=directed, backend=backend,
                )

            return jax.vmap(one)(pts, valid)

        fn = jax.jit(shard_map(
            _local, mesh=ctx.mesh,
            in_specs=(P(), P("corpus"), P("corpus")),
            out_specs=P("corpus"),
            check_vma=False,
        ))
        ctx._stage1[key] = fn
    cert = fn(
        q,
        jnp.take(bucket.points, take, axis=0),
        jnp.take(bucket.valid, take, axis=0),
    )
    return type(cert)(*(np.asarray(f)[inv][:lanes] for f in cert))


def merge_topk(lb: np.ndarray, ub: np.ndarray, alive: np.ndarray, k: int):
    """Cross-shard certified top-k merge.

    The per-shard stage-1 certificates were already folded into the global
    (lb, ub) interval state; the merge is the global re-application of the
    cascade's prune rule — τ = k-th smallest certified upper bound over
    the WHOLE corpus, survivors ``lb ≤ τ`` — identical to the
    single-device stage-1 epilogue, which is what makes the sharded
    frontier feed the unchanged stage 2.  Returns ``(tau, still_alive)``.
    """
    tau = _cascade._kth_smallest(ub, k)
    return tau, alive & (lb <= tau)
