"""Registered implementations for every served (variant, method, backend).

Each implementation is a thin adapter from the uniform front-door contract
onto the pre-existing estimator it serves — the math lives where it always
did (``repro.core.exact``, ``repro.core.prohd``, ``repro.core.variants``,
``repro.core.sampling``, ``repro.core.adaptive``, ``repro.core.distributed``,
``repro.kernels.hausdorff.ops``).  Adapters MUST call those entry points
with pass-through arguments so a front-door dispatch is bit-for-bit equal
to the direct call (the matrix test in tests/test_hd_api.py enforces
this).

Contract::

    impl(a, b, ctx: DispatchContext) -> (value, lower, upper, stats)

where ``lower``/``upper`` are certified bounds on the true distance (or
None when the method has no guarantee) and ``stats`` is a dict pytree of
method-specific numerics.

The currently-served matrix (everything else raises the structured
``UnsupportedCombination``)::

    (hausdorff, exact):    dense  tiled  fused_pallas  distributed
    (hausdorff, prohd):    dense  tiled  fused_pallas  distributed
    (hausdorff, sampling):        tiled
    (hausdorff, adaptive):        tiled
    (directed,  exact):    dense  tiled  fused_pallas
    (partial,   exact):    dense  tiled  fused_pallas
    (chamfer,   exact):    dense  tiled  fused_pallas
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import adaptive as adaptive_mod
from repro.core import exact, sampling, tile_bounds, variants
# NB: import the function by module path — the ``repro.core`` package
# attribute ``prohd`` is the function, not the module.
from repro.core.prohd import prohd as _prohd_call
from repro.hd.config import HDConfig
from repro.hd.registry import register

__all__ = ["DispatchContext"]


class DispatchContext(NamedTuple):
    """Everything an implementation may need beyond the two clouds.

    Masking, padding and block-size resolution used to be re-derived by
    every caller; the engine resolves them ONCE and hands the result down.
    """

    valid_a: jax.Array | None
    valid_b: jax.Array | None
    key: jax.Array | None
    cfg: HDConfig
    block_a: int
    block_b: int
    mesh: Any | None
    batch_axes: tuple[str, ...]
    # (proj_a, proj_b) per-row projections onto shared unit directions
    # (column 0 primary); enables certified projection pruning + the
    # skip_fraction stat on the exact scan backends.
    prune_projs: tuple[jax.Array, jax.Array] | None


def _reject_masks(ctx: DispatchContext, method: str) -> None:
    if ctx.valid_a is not None or ctx.valid_b is not None:
        raise ValueError(
            f"method={method!r} does not accept masks=; it selects/samples its "
            "own subsets from full clouds (pre-filter the inputs, or use the "
            "serving layer's masked path)"
        )


def _require_key(ctx: DispatchContext, method: str) -> jax.Array:
    if ctx.key is None:
        raise ValueError(f"method={method!r} is randomized and requires key=")
    return ctx.key


def _skip_stats(
    a, b, ctx: DispatchContext, *, directed: bool, block_a: int, block_b: int
) -> dict:
    """skip_fraction of the tile grid under pruning.

    ``block_a``/``block_b`` must be the grid the dispatched scan REALLY
    ran (each backend clamps differently), so the diagnostic reflects the
    pruning that actually happened.  Recomputes the prune tables
    (O(n log n + n·D), negligible next to the scan) so stats never perturb
    the hot path's own table assembly.
    """
    if ctx.prune_projs is None:
        return {}
    proj_a, proj_b = ctx.prune_projs
    tables = tile_bounds.prune_tables(
        a, proj_a, ctx.valid_a, b, proj_b, ctx.valid_b,
        block_a, block_b, directed=directed,
    )
    return {"skip_fraction": tile_bounds.skip_fraction(tables)}


# ---------------------------------------------------------------------------
# variant=hausdorff / directed, method=exact
# ---------------------------------------------------------------------------


@register("hausdorff", "exact", "dense")
def _hausdorff_exact_dense(a, b, ctx):
    v = exact.hausdorff_dense(a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b)
    return v, v, v, {}


@register("hausdorff", "exact", "tiled")
def _hausdorff_exact_tiled(a, b, ctx):
    v = exact.hausdorff_fused_tiled(
        a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b,
        block_a=ctx.block_a, block_b=ctx.block_b, prune_projs=ctx.prune_projs,
    )
    # the pure-JAX scan clamps blocks to the cloud sizes
    stats = _skip_stats(
        a, b, ctx, directed=False,
        block_a=min(ctx.block_a, a.shape[0]), block_b=min(ctx.block_b, b.shape[0]),
    )
    return v, v, v, stats


@register("hausdorff", "exact", "fused_pallas")
def _hausdorff_exact_pallas(a, b, ctx):
    from repro.kernels.hausdorff import ops as hd_ops

    v = hd_ops.hausdorff(
        a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b,
        prune_projs=ctx.prune_projs, block_a=ctx.block_a, block_b=ctx.block_b,
        interpret=ctx.cfg.interpret,
    )
    # the kernel wrapper snaps blocks to power-of-two tile edges
    stats = _skip_stats(
        a, b, ctx, directed=False,
        block_a=hd_ops.fit_block(ctx.block_a, a.shape[0]),
        block_b=hd_ops.fit_block(ctx.block_b, b.shape[0]),
    )
    return v, v, v, stats


@register("directed", "exact", "dense")
def _directed_exact_dense(a, b, ctx):
    v = exact.directed_hd_dense(a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b)
    return v, v, v, {}


@register("directed", "exact", "tiled")
def _directed_exact_tiled(a, b, ctx):
    v = exact.directed_hd_tiled(
        a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b,
        block=ctx.block_b, prune_projs=ctx.prune_projs,
    )
    # the directed scan keeps all queries in ONE block (a single cut_a)
    stats = _skip_stats(
        a, b, ctx, directed=True,
        block_a=a.shape[0], block_b=min(ctx.block_b, b.shape[0]),
    )
    return v, v, v, stats


@register("directed", "exact", "fused_pallas")
def _directed_exact_pallas(a, b, ctx):
    from repro.kernels.hausdorff import ops as hd_ops

    v = hd_ops.directed_hausdorff(
        a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b,
        prune_projs=ctx.prune_projs, block_a=ctx.block_a, block_b=ctx.block_b,
        interpret=ctx.cfg.interpret,
    )
    stats = _skip_stats(
        a, b, ctx, directed=True,
        block_a=hd_ops.fit_block(ctx.block_a, a.shape[0]),
        block_b=hd_ops.fit_block(ctx.block_b, b.shape[0]),
    )
    return v, v, v, stats


@register("hausdorff", "exact", "distributed")
def _hausdorff_exact_distributed(a, b, ctx):
    from repro.core import distributed as dist

    mesh = _require_mesh(ctx, "exact")
    A, B = _sharded_pair(a, b, ctx)
    v = dist.distributed_exact_hd(mesh, A, B, batch_axes=ctx.batch_axes)
    return v, v, v, {}


# ---------------------------------------------------------------------------
# variant=partial / chamfer, method=exact
# ---------------------------------------------------------------------------
# Both reduce the SAME fused bidirectional min-d² scan, so every single-
# device backend of that scan serves them: the Pallas kernel, its pure-JAX
# tiled mirror, and the dense reference.


def _min_sqdists_both(a, b, ctx, backend: str):
    if backend == "fused_pallas":
        from repro.kernels.hausdorff import ops as hd_ops

        return hd_ops.fused_min_sqdists(
            a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b,
            block_a=ctx.block_a, block_b=ctx.block_b, interpret=ctx.cfg.interpret,
        )
    if backend == "tiled":
        return exact.fused_min_sqdists_tiled(
            a, b, valid_a=ctx.valid_a, valid_b=ctx.valid_b,
            block_a=ctx.block_a, block_b=ctx.block_b,
        )
    d2 = exact.pairwise_sqdist(a, b)
    pos = jnp.float32(jnp.inf)
    if ctx.valid_b is not None:
        d2 = jnp.where(ctx.valid_b[None, :], d2, pos)
    min_a = jnp.min(d2, axis=1)
    if ctx.valid_a is not None:
        d2 = jnp.where(ctx.valid_a[:, None], d2, pos)
    min_b = jnp.min(d2, axis=0)
    return min_a, min_b


def _register_minscan_variant(variant: str, reduce_fn):
    for backend in ("dense", "tiled", "fused_pallas"):

        @register(variant, "exact", backend)
        def impl(a, b, ctx, *, _backend=backend):
            v = reduce_fn(a, b, ctx, _backend)
            return v, None, None, {}

    return reduce_fn


def _partial_reduce(a, b, ctx, backend):
    # Same reduction as variants.partial_hausdorff over whichever backend's
    # fused scan was dispatched — ctx blocks/interpret are honoured (tile
    # values are bitwise block-independent, so this stays equal to the
    # direct call at any block choice).
    min_a, min_b = _min_sqdists_both(a, b, ctx, backend)
    return jnp.maximum(
        variants.quantile_reduce(min_a, ctx.valid_a, a.shape[0], ctx.cfg.quantile),
        variants.quantile_reduce(min_b, ctx.valid_b, b.shape[0], ctx.cfg.quantile),
    )


def _chamfer_reduce(a, b, ctx, backend):
    min_a, min_b = _min_sqdists_both(a, b, ctx, backend)
    return variants.mean_min_dist(min_a, ctx.valid_a) + variants.mean_min_dist(
        min_b, ctx.valid_b
    )


_register_minscan_variant("partial", _partial_reduce)
_register_minscan_variant("chamfer", _chamfer_reduce)


# ---------------------------------------------------------------------------
# method=prohd
# ---------------------------------------------------------------------------


def _prohd_bounds(est, pc):
    lower = est.hd_proj if pc.compute_projected else None
    upper = (
        est.hd_proj + est.bound
        if (pc.compute_projected and pc.compute_bound)
        else None
    )
    return lower, upper


def _register_prohd(backend: str):
    @register("hausdorff", "prohd", backend)
    def impl(a, b, ctx, *, _backend=backend):
        _reject_masks(ctx, "prohd")
        pc = ctx.cfg.prohd_config(_backend)
        if obs.enabled():
            obs.xla.note(_prohd_call, a, b, pc, key=ctx.key)
        est = _prohd_call(a, b, pc, key=ctx.key)
        lower, upper = _prohd_bounds(est, pc)
        stats = {"estimate": est, "n_sel_a": est.n_sel_a, "n_sel_b": est.n_sel_b}
        return est.hd, lower, upper, stats


for _b in ("dense", "tiled", "fused_pallas"):
    _register_prohd(_b)


@register("hausdorff", "prohd", "distributed")
def _prohd_distributed(a, b, ctx):
    from repro.core import distributed as dist

    mesh = _require_mesh(ctx, "prohd")
    pc = ctx.cfg.prohd_config("tiled")
    A, B = _sharded_pair(a, b, ctx)
    hd, n_sel_a, n_sel_b = dist.distributed_prohd(
        mesh, A, B, pc, batch_axes=ctx.batch_axes
    )
    # The distributed path does not compute the projected certificate.
    return hd, None, None, {"n_sel_a": n_sel_a, "n_sel_b": n_sel_b}


# ---------------------------------------------------------------------------
# method=sampling / adaptive
# ---------------------------------------------------------------------------


@register("hausdorff", "sampling", "tiled")
def _sampling_tiled(a, b, ctx):
    _reject_masks(ctx, "sampling")
    key = _require_key(ctx, "sampling")
    if ctx.cfg.sampler not in ("random", "systematic"):
        raise ValueError(f"unknown sampler {ctx.cfg.sampler!r}")
    fn = (
        sampling.random_sampling_hd
        if ctx.cfg.sampler == "random"
        else sampling.systematic_sampling_hd
    )
    hd, n = fn(key, a, b, ctx.cfg.alpha, block=ctx.block_b)
    # Sampled-vs-sampled HD can land on either side of the truth (the
    # inner min inflates, the outer max deflates): no certified bounds.
    return hd, None, None, {"n_sampled": n}


@register("hausdorff", "adaptive", "tiled")
def _adaptive_tiled(a, b, ctx):
    _reject_masks(ctx, "adaptive")
    res = adaptive_mod.prohd_with_budget(
        a,
        b,
        budget=ctx.cfg.budget,
        relative=ctx.cfg.budget_relative,
        alpha0=ctx.cfg.adaptive_alpha0,
        max_alpha=ctx.cfg.adaptive_max_alpha,
        max_steps=ctx.cfg.adaptive_max_steps,
        key=ctx.key,
    )
    est = res.estimate
    stats = {
        "adaptive": res,
        "estimate": est,
        "n_sel_a": est.n_sel_a,
        "n_sel_b": est.n_sel_b,
    }
    return est.hd, est.hd_proj, est.hd_proj + est.bound, stats


# ---------------------------------------------------------------------------
# distributed plumbing
# ---------------------------------------------------------------------------


def _require_mesh(ctx: DispatchContext, method: str):
    if ctx.mesh is None:
        raise ValueError(
            f"backend='distributed' (method={method!r}) requires mesh=; pass the "
            "jax.sharding.Mesh whose batch axes row-shard the clouds"
        )
    return ctx.mesh


def _sharded_pair(a, b, ctx: DispatchContext):
    from repro.core.distributed import ShardedCloud

    va = ctx.valid_a if ctx.valid_a is not None else jnp.ones((a.shape[0],), jnp.bool_)
    vb = ctx.valid_b if ctx.valid_b is not None else jnp.ones((b.shape[0],), jnp.bool_)
    return ShardedCloud(a, va), ShardedCloud(b, vb)
