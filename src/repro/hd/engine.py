"""The front door: ``set_distance`` and the jit/vmap-friendly ``HDEngine``.

One entry point for every set-distance query the framework answers::

    from repro.hd import HDConfig, set_distance

    res = set_distance(a, b)                               # exact, auto backend
    res = set_distance(a, b, method="prohd",
                       config=HDConfig(alpha=0.02))        # certified estimate
    res = set_distance(a, b, variant="chamfer")            # smooth drift signal

Every call returns the uniform :class:`repro.hd.result.HDResult`.  The
engine resolves ``backend="auto"`` and the block sizes ONCE per call from
static facts (shapes, D, device kind, mesh) — the consolidation point for
the masking / padding / block-size logic that serving, streaming, training
and the examples previously each re-derived.

``HDEngine`` freezes one dispatch decision into a hashable, all-static
pytree, so it can be closed over by (or passed into) ``jax.jit`` /
``jax.vmap`` — the serving layer vmaps engine calls across request
batches.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import numpy as np

from repro import obs
from repro.hd import registry, resolver
from repro.hd.config import HDConfig
from repro.hd.methods import DispatchContext
from repro.hd.result import HDMeta, HDResult
from repro.obs.trace import _NOOP as _INERT

__all__ = ["set_distance", "HDEngine"]


def _unpack_masks(masks):
    if masks is None:
        return None, None
    valid_a, valid_b = masks
    return valid_a, valid_b


def _reject_nonfinite(name: str, cloud, valid) -> None:
    """Front-door input validation: NaN/Inf on a VALID row is an error.

    Masked-out rows may legitimately hold garbage (the padding
    convention), so the check is mask-aware.  No-ops under tracing —
    tracers carry no values to validate (HDEngine inside jit/vmap rides
    through untouched).
    """
    if isinstance(cloud, jax.core.Tracer) or isinstance(valid, jax.core.Tracer):
        return
    finite = np.isfinite(np.asarray(cloud)).all(axis=-1)
    if valid is not None:
        finite = finite | ~np.asarray(valid)
    if not bool(finite.all()):
        bad = int(np.argmin(finite))
        raise ValueError(
            f"cloud {name!r} has non-finite coordinates on valid row {bad} "
            "(NaN/Inf); certified intervals are undefined over them — "
            "clean the input, mask the row out, or pass validate=False"
        )


def set_distance(
    a,
    b,
    *,
    variant: str = "hausdorff",
    method: str = "exact",
    backend: str = "auto",
    masks: tuple[Any, Any] | None = None,
    config: HDConfig | None = None,
    key: jax.Array | None = None,
    mesh: Any | None = None,
    batch_axes: tuple[str, ...] = ("data",),
    prune_projs: tuple[Any, Any] | None = None,
    measure: bool = False,
    validate: bool = True,
) -> HDResult:
    """Compute a set distance between clouds ``a`` (n_a, D) and ``b`` (n_b, D).

    variant  — hausdorff | directed | partial | chamfer
    method   — exact | prohd | sampling | adaptive
    backend  — dense | tiled | fused_pallas | distributed | auto (default;
               resolved from (n, m, D, device, mesh) by repro.hd.resolver)
    masks    — optional (valid_a, valid_b) row-validity masks (True = real
               row); honoured exactly by the exact variants, rejected by
               subset-selecting methods
    config   — HDConfig with method knobs (alpha, quantile, budget, blocks…)
    key      — PRNG key for randomized methods (sampling; prohd's
               randomized PCA backends)
    mesh     — jax.sharding.Mesh, required by (and triggering, under auto)
               the distributed backend
    prune_projs — optional (proj_a, proj_b) projections enabling certified
               projection pruning on the exact scan backends (adds a
               ``skip_fraction`` stat)
    measure  — block until ready and record wall time in ``meta.elapsed_s``
               (ignored under tracing)
    validate — reject non-finite coordinates on VALID rows with a
               ValueError (default True): a NaN/Inf point flows straight
               into the kernels and silently poisons every "certified"
               interval — only masked-OUT garbage is handled (the
               poisoned-norm convention).  Skipped automatically under
               tracing (tracers carry no values); ``validate=False`` is
               the escape hatch for pre-validated hot paths.

    Returns an :class:`HDResult`; unserved (variant, method, backend) cells
    raise the structured :class:`repro.hd.registry.UnsupportedCombination`.
    """
    registry.validate_axes(variant, method, backend)
    cfg = config if config is not None else HDConfig()
    valid_a, valid_b = _unpack_masks(masks)
    # Spans only for concrete calls: under jit/vmap nothing runs here.
    traced = obs.enabled() and not (isinstance(a, jax.core.Tracer)
                                    or isinstance(b, jax.core.Tracer))
    with obs.span("hd.set_distance", variant=variant, method=method) if traced else _INERT as root:
        if validate:
            with obs.span("hd.validate") if traced else _INERT as sp:
                _reject_nonfinite("a", a, valid_a)
                _reject_nonfinite("b", b, valid_b)
                if traced:  # what the check copied off the device
                    sp.set(bytes=sum(int(x.nbytes) for x in (a, b, valid_a, valid_b)
                                     if isinstance(x, jax.Array)))
        n_a, d = a.shape
        n_b = b.shape[0]

        if backend == "auto":
            n_devices = getattr(mesh, "size", 1) if mesh is not None else 1
            backend = resolver.resolve_backend(
                variant, method, n_a, n_b, d,
                device_kind=resolver.default_device_kind(), n_devices=n_devices,
            )
        if traced:
            root.set(backend=backend, n_a=n_a, n_b=n_b, d=d)
        impl = registry.resolve(variant, method, backend)

        block_a, block_b = cfg.block_a, cfg.block_b
        if block_a is None or block_b is None:
            rba, rbb = resolver.resolve_block_sizes(
                n_a, n_b, d,
                device_kind=resolver.default_device_kind(), backend=backend,
            )
            block_a = rba if block_a is None else block_a
            block_b = rbb if block_b is None else block_b

        ctx = DispatchContext(
            valid_a=valid_a, valid_b=valid_b, key=key, cfg=cfg,
            block_a=block_a, block_b=block_b, mesh=mesh,
            batch_axes=tuple(batch_axes), prune_projs=prune_projs,
        )

        timing = measure and not isinstance(a, jax.core.Tracer)
        t0 = time.perf_counter() if timing else 0.0
        with obs.span("hd.dispatch") if traced else _INERT:
            value, lower, upper, stats = impl(a, b, ctx)
        elapsed = None
        if timing:
            jax.block_until_ready(value)
            elapsed = time.perf_counter() - t0

    meta = HDMeta(
        variant=variant, method=method, backend=backend,
        block_a=block_a, block_b=block_b, elapsed_s=elapsed,
    )
    return HDResult(value=value, lower=lower, upper=upper, stats=stats, meta=meta)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=[],
    meta_fields=["variant", "method", "backend", "config"],
)
@dataclasses.dataclass(frozen=True)
class HDEngine:
    """One frozen dispatch decision, callable like the estimator it names.

    All fields are static pytree metadata, so an engine instance is
    hashable and crosses jit/vmap boundaries for free::

        engine = HDEngine(method="prohd", config=HDConfig(alpha=0.05))
        batched = jax.vmap(lambda a, b: engine(a, b).value)
    """

    variant: str = "hausdorff"
    method: str = "exact"
    backend: str = "auto"
    config: HDConfig = HDConfig()

    def __call__(
        self,
        a,
        b,
        *,
        masks=None,
        key=None,
        mesh=None,
        batch_axes: tuple[str, ...] = ("data",),
        prune_projs=None,
        measure: bool = False,
        validate: bool = True,
    ) -> HDResult:
        return set_distance(
            a, b,
            variant=self.variant, method=self.method, backend=self.backend,
            masks=masks, config=self.config, key=key, mesh=mesh,
            batch_axes=batch_axes, prune_projs=prune_projs, measure=measure,
            validate=validate,
        )
