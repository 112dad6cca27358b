"""Certified bound-cascade top-k set-distance search over a SetStore.

Three stages, each a strictly tighter (and strictly more expensive)
certified interval around every candidate's true distance to the query:

  stage 0 — **summary bounds, whole corpus, one shot.**  From per-set
      summaries alone (centroid, centroid radii, projection intervals on
      the store's direction bank):
        lower: projection-interval gaps — an interval ENDPOINT is a real
            projected point, and projections contract distances, so its
            1-D gap to the other set's interval hull lower-bounds H;
        upper: triangle inequality through the centroids —
            dist(a, B) ≤ ||a − c_A|| + ||c_A − c_B|| + min_b ||c_B − b||.
      Vectorized over all N stored sets without touching a single point.
  stage 1 — **vmapped bucketed masked ProHD** on the survivors: the
      full-inner subset estimate (never overestimates → certified lower
      bound), max_u H_u (lower), and the Eq. 5 additive bound (upper),
      one vmapped call per storage bucket.
  stage 2 — **exact refinement** of the remaining frontier, in two beats
      under ``stage2="batched"`` (the default):

      2a. one vmapped masked EXACT pass per surviving bucket
          (``core/masked.masked_exact_hd`` over the padded slabs, batch
          padded to a power of two).  The padded value is exact arithmetic
          on the valid rows, but its GEMM runs at a different shape than
          the raw oracle's, and fp32 GEMM bits are NOT invariant across
          shapes (the conformance harness demonstrates a real one-ulp
          counterexample on CPU) — so 2a's result enters the cascade as a
          certified interval ``value ± fp_margin(D, scale)``, never as
          "the" value.  The margin is the conformance-pinned bound on how
          far two fp32 exact computations of the same distance can land
          apart.  One such pass collapses every frontier interval to
          ±margin at a jit-cache cost of one entry per distinct (bucket
          capacity, batch size) pair — the per-candidate dispatch overhead
          of the historical loop is gone from the hot path.
      2b. raw resolution of the candidates still straddling the top-k
          boundary after 2a — ascending-lower-bound through the exact
          ``repro.hd`` front door on RAW (unpadded) points, exactly the
          historical loop, but now over ≈ k candidates (+ exact ties)
          instead of the whole frontier.  Every RETURNED value therefore
          remains bit-for-bit the number brute force computes, independent
          of padding layout, batch composition, or stage-2 mode.

      ``stage2="sequential"`` keeps the pure historical loop (every
      frontier candidate raw-refined one at a time); both modes return
      identical bits, and ``scripts/check.sh`` gates identity, jit-trace
      reduction and wall clock.

The prune rule is the certified one throughout: a candidate dies exactly
when its certified lower bound exceeds τ, the current k-th smallest
certified upper bound over all candidates.  Soundness: lb_i > τ implies
ub_i > τ, so the k candidates whose upper bounds define τ are all others,
and each of their true values is ≤ τ < lb_i ≤ value_i — at least k
candidates beat i outright, ties included.  Stage 2 always drains (every
alive candidate is refined or pruned), so the returned top-k — ranked by
(value, id) — is **provably identical to brute force**, which the
hypothesis suite and the ``scripts/check.sh`` gate both assert.

Floating point: stage-0/1 bounds are certified for exact arithmetic, and
the prune rule compares them against fp32 refined values — so the margin
must absorb BOTH fp error sources, measured in the pair's MAGNITUDE scale
(``bound_scale`` = Σ ||centroid|| + r_max, which dominates every point
norm, projection and distance in play):

- the bounds' own subtractions err by O(eps)·scale absolutely (a tiny
  interval gap between huge projections — a relative-in-the-result margin
  would miss this entirely);
- the exact oracle's GEMM-form ``||a||² − 2ab + ||b||²`` errs by
  O((D+2)·eps)·scale² in d², i.e. up to ``sqrt((D+2)·eps)·scale`` in the
  DISTANCE when the true distance is near zero — the dominant term.

``certified_margins`` therefore widens by ``2·sqrt((D+2)·eps_fp32)·scale``
plus a 1e-6 absolute floor.  At sane magnitudes (unit-ish data) this is
~1e-3·scale and invisible; at pathological magnitudes (coordinates ≫ 1e4)
it honestly reports that fp32 can no longer separate candidates — pruning
stops and the cascade degrades to brute force, preserving the identity
guarantee instead of silently breaking it.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import masked, projections
from repro.hd import resolver
from repro.hd.config import HDConfig
from repro.hd.result import HDMeta
from repro.index.store import SetStore, SetSummary, bucket_capacity
from repro.obs import trace as _obs
from repro.obs.metrics import record_stats as _record_stats
from repro.reliability import faults as _faults
from repro.reliability.errors import BackendUnavailable

__all__ = [
    "SearchResult",
    "SEARCH_VARIANTS",
    "SEARCH_METHODS",
    "SEARCH_MODES",
    "STAGE2_MODES",
    "ON_FAULT_MODES",
    "anytime_frontier",
    "certified_recall",
    "interval_bounds",
    "bound_scale",
    "certified_margins",
    "fp_margin",
    "fp_value_margin",
    "search",
]

SEARCH_VARIANTS = ("hausdorff", "directed")
SEARCH_METHODS = ("cascade", "exact")
SEARCH_MODES = ("exact", "anytime")
STAGE2_MODES = ("batched", "sequential")
ON_FAULT_MODES = ("degrade", "raise")

# Injection points swept by tests/test_fault_injection.py: one per cascade
# stage (raise models a mid-stage failure, slow a straggler) plus the
# per-call backend gate (backend_down models one masked backend dying —
# the cascade must fall back to the next registered one).
_POINT_STAGE0 = _faults.declare_point(
    "cascade.stage0", "summary-bound stage — failure here precedes ANY "
    "certified state, so it always surfaces as a typed error")
_POINT_STAGE1 = _faults.declare_point(
    "cascade.stage1", "masked-ProHD tightening — failure degrades to the "
    "stage-0 (or partially tightened) certified intervals")
_POINT_STAGE2A = _faults.declare_point(
    "cascade.stage2a", "batched exact tightening — failure degrades to the "
    "best certified intervals reached")
_POINT_STAGE2B = _faults.declare_point(
    "cascade.stage2b", "raw exact refinement — failure degrades; already-"
    "refined candidates keep their exact values")
_POINT_BACKEND = _faults.declare_point(
    "cascade.backend", "masked-backend availability gate before every "
    "bucket-granularity dispatch (match= the backend name)")
_POINT_ANYTIME = _faults.declare_point(
    "cascade.anytime", "anytime (ε/budget) escalation ladder — failure "
    "degrades to the best certified intervals reached, exactly like the "
    "exact cascade's mid-stage faults")

# Exceptions the cascade may degrade on (on_fault="degrade"): the typed
# reliability family (all RuntimeError subclasses) plus the raw XLA/device
# failure classes run_with_recovery retries in training.  Programming
# errors (ValueError/TypeError) always propagate.
_DEGRADABLE = (RuntimeError, FloatingPointError)


# THE cascade wall clock.  The deadline budget, ``stats["elapsed_s"]`` and
# the obs latency spans must all be comparable on one axis (historically
# the budget ran on time.monotonic while elapsed ran on time.perf_counter,
# so ``elapsed ≤ deadline_s + margin`` was not a well-formed statement) —
# every wall-time read in this module and in ``multiquery`` goes through
# this hook.  Module-level so tests can monkeypatch a fake clock.
_now = time.monotonic


class _Budget:
    """Monotonic wall-clock deadline; None = unbounded."""

    def __init__(self, deadline_s: float | None):
        self.t0 = _now()
        self.deadline = None if deadline_s is None else self.t0 + float(deadline_s)

    def expired(self) -> bool:
        return self.deadline is not None and _now() >= self.deadline


class _DeadlineHit(Exception):
    """Internal unwind signal: deadline expired, assemble the degraded
    result.  Deliberately NOT a RuntimeError so the fault-degrade handler
    can never confuse it with a real failure."""

# fp safety margins applied to every certified bound (see module docstring).
_EPS32 = float(np.finfo(np.float32).eps)
_ABS = 1e-6


def _margin_factor(dim: int) -> float:
    """Per-unit-scale widening: covers the exact oracle's worst-case
    distance error sqrt((D+2)·eps)·scale with a 2x safety factor."""
    return 2.0 * float(np.sqrt((dim + 2) * _EPS32))


def fp_margin(dim: int, scale):
    """THE pinned fp32 margin: ``2·sqrt((dim+2)·eps32)·scale + 1e-6``.

    The single source of truth for "how far apart may two fp32 exact-HD
    computations of the same quantity legitimately land": it covers the
    GEMM-form ``||a||² − 2ab + ||b||²`` cancellation error of operands
    whose magnitudes are dominated by ``scale`` (see the module
    docstring's error budget).  ``certified_margins`` widens the cascade's
    bounds by exactly this; the conformance harness pins cross-backend
    disagreement to it wherever bitwise equality is not the contract.
    """
    return scale * _margin_factor(dim) + _ABS


def fp_value_margin(dim: int, scale, value):
    """Value-aware sharpening of :func:`fp_margin` — still fully certified.

    Both margins bound how far apart two fp32 exact-HD computations of the
    same pair can land; ``fp_margin`` is the near-zero worst case.  Away
    from zero the sqrt de-amplifies the GEMM's d² error: with
    ``E = (dim+2)·eps32·scale²`` bounding ``|d̂² − d²|``, the identity
    ``|√x − √y| = |x − y|/(√x + √y)`` gives a per-computation distance
    error of ``min(√E, E/v)``.  For an observed value ``v̂`` (one of the
    two computations), the other and the truth all live within
    ``v̂ ± √E``, so a two-sided envelope of

        2·√E                      if v̂ ≤ 2·√E   (the fp_margin regime)
        2·E/(v̂ − √E) + 1e-6      otherwise

    is certified — and orders of magnitude tighter than ``fp_margin`` at
    ordinary distances, which is what lets the batched stage 2a actually
    separate a frontier whose value gaps are small relative to ``scale``.
    Host-side math: broadcasts over anything ``np.asarray`` accepts (jax
    arrays included) and always computes in float64 — ``jnp`` would
    silently truncate to fp32 without x64 — returning numpy.  Always
    ≤ ``fp_margin + √E`` and monotone in ``scale``.
    """
    e = (dim + 2) * _EPS32 * np.asarray(scale, dtype=np.float64) ** 2
    sqrt_e = np.sqrt(e)
    lo = np.maximum(np.asarray(value, dtype=np.float64) - sqrt_e, 0.0)
    return np.where(lo > sqrt_e, 2.0 * e / np.maximum(lo, 1e-300), 2.0 * sqrt_e) + _ABS


@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Top-k result of a corpus search — the corpus analogue of HDResult.

    ids/values are ranked ascending by (value, id); in the normal
    (non-degraded) case every returned value is EXACT (stage-2 refined),
    so ``lower == upper == values``.  ``stats`` carries the cascade's work
    accounting.  ``meta`` reuses HDMeta with one documented exception to
    its pairwise contract: the exact refines re-resolve per candidate
    set's shape, so there is no single concrete dispatch — ``backend`` is
    recorded AS REQUESTED (possibly "auto") and the per-refine block sizes
    as 0.

    **Degraded results** (``degraded=True``; a deadline expired or a
    mid-cascade fault was absorbed under ``on_fault="degrade"``): the
    certificate weakens but never lies — every returned candidate carries
    the certified interval ``[lower_i, upper_i]`` that provably contains
    its true distance (the same bounds the cascade prunes with), ranked
    ascending by (upper, id); ``values`` holds the exact distance where
    stage 2 got that far and the certified upper bound otherwise.  The
    top-k MEMBERSHIP may differ from brute force's — that is exactly what
    the flag says — but a degraded result is never presented as an exact
    one.  ``stage_reached`` names the deepest stage that contributed
    tightening ("stage0" | "stage1" | "stage2a" | "stage2b"), or
    "complete" for a fully drained (non-degraded) cascade.

    **Anytime results** (``meta.mode == "anytime"`` with ε > 0 or a
    budget): membership is the current top-k by certified upper bound,
    ``values`` holds the exact distance where the ladder resolved a hit
    and the certified point estimate (clipped into ``[lower, upper]``)
    otherwise, and every per-hit interval still provably contains the true
    distance.  ``certified_recall_at_k`` is the fraction of returned hits
    PROVABLY in the exact brute-force top-k from the intervals alone (hit
    ``i`` is certified iff at most k−1 other candidates have
    ``lb_j ≤ ub_i`` — sound under the (value, id) tie-break; see
    :func:`certified_recall`): 1.0 for complete exact results by
    construction, never an overestimate of the true recall anywhere else.
    """

    ids: np.ndarray       # (k,) int32 set ids
    values: np.ndarray    # (k,) fp32 exact distances (degraded: best known)
    stats: dict[str, Any]
    meta: HDMeta
    lower: np.ndarray = None    # (k,) fp64 certified lower bounds
    upper: np.ndarray = None    # (k,) fp64 certified upper bounds
    degraded: bool = False
    stage_reached: str = "complete"
    certified_recall_at_k: float = 1.0

    def __post_init__(self):
        # default the certificate to the exact values (lower == upper)
        if self.lower is None:
            object.__setattr__(self, "lower", self.values.astype(np.float64))
        if self.upper is None:
            object.__setattr__(self, "upper", self.values.astype(np.float64))


def interval_bounds(sa: SetSummary, sb: SetSummary, *, directed: bool = False):
    """Certified (lower, upper) distance bounds from summaries alone.

    Broadcasts: pass one plain summary and one (N,)-stacked summary to get
    (N,) bounds (stage 0), or two plain summaries for a single pair (the
    drift monitor's fast pre-check).  RAW bounds — callers must apply
    :func:`certified_margins` before pruning on them.
    """
    dc = jnp.sqrt(jnp.maximum(jnp.sum((sa.centroid - sb.centroid) ** 2, axis=-1), 0.0))
    if directed:
        ub = dc + sa.r_max + sb.r_min
    else:
        ub = dc + jnp.maximum(sa.r_max + sb.r_min, sb.r_max + sa.r_min)

    def gap(x, lo, hi):
        return jnp.maximum(jnp.maximum(lo - x, x - hi), 0.0)

    g = jnp.maximum(
        gap(sa.proj_lo, sb.proj_lo, sb.proj_hi),
        gap(sa.proj_hi, sb.proj_lo, sb.proj_hi),
    )
    if not directed:
        g = jnp.maximum(
            g,
            jnp.maximum(
                gap(sb.proj_lo, sa.proj_lo, sa.proj_hi),
                gap(sb.proj_hi, sa.proj_lo, sa.proj_hi),
            ),
        )
    lb = jnp.max(g, axis=-1)
    return lb, ub


_interval_bounds_jit = functools.partial(jax.jit, static_argnames=("directed",))(
    interval_bounds
)


def bound_scale(sa: SetSummary, sb: SetSummary):
    """Per-pair magnitude that dominates every quantity entering the bounds.

    ``||centroid|| + r_max`` upper-bounds the norm of every point of a set,
    hence (unit directions) every projection, every centroid coordinate and
    every distance the bounds subtract — the right yardstick for absolute
    fp32 error.  Broadcasts like :func:`interval_bounds`.
    """
    na = jnp.sqrt(jnp.maximum(jnp.sum(sa.centroid**2, axis=-1), 0.0)) + sa.r_max
    nb = jnp.sqrt(jnp.maximum(jnp.sum(sb.centroid**2, axis=-1), 0.0)) + sb.r_max
    return na + nb


_bound_scale_jit = jax.jit(bound_scale)


def certified_margins(lb, ub, scale, dim: int):
    """Widen raw certified bounds so fp32 rounding cannot flip a prune.

    ``scale`` is the :func:`bound_scale` of the pair (broadcastable) and
    ``dim`` the point dimension: the widening is
    ``2·sqrt((dim+2)·eps_fp32)·scale + 1e-6``, ABSOLUTE on both sides —
    it must cover both the bounds' subtraction error AND the exact
    oracle's own GEMM cancellation error (see the module docstring), both
    of which are proportional to the operand magnitudes, not to the
    (possibly tiny) result.
    """
    xp = jnp if isinstance(lb, jnp.ndarray) else np
    pad = fp_margin(dim, scale)
    return xp.maximum(lb - pad, 0.0), ub + pad


@functools.partial(jax.jit, static_argnames=("alpha", "m", "directed", "backend"))
def _stage1_batch(
    q, pts, valid, *, alpha: float, m: int, directed: bool, backend: str = "tiled"
):
    """Masked ProHD certificates, query vs a (S, C, D) candidate slab.

    ``backend`` routes the certificates' exact subset passes through the
    resolved masked reduction (``EXACT_MASKED_BACKENDS``) — stage 1 rides
    the same kernel family as stage 2a.
    """
    with jax.named_scope("cascade.stage1_batch"):
        va = jnp.ones((q.shape[0],), jnp.bool_)

        def one(p, v):
            return masked.masked_prohd_certified(
                q, va, p, v, alpha=alpha, m=m, directed=directed, backend=backend
            )

        return jax.vmap(one)(pts, valid)


@functools.partial(
    jax.jit, static_argnames=("directed", "backend", "block_a", "block_b")
)
def _stage2_batch(
    q, pts, valid, gate_lb=None, gate_cut=None, *, directed, backend,
    block_a, block_b,
):
    """EXACT masked HD, query vs a (B, cap, D) candidate slab — one bucket's
    whole surviving frontier measured in a single jitted call.

    Exact arithmetic over the valid rows of every lane; each lane's result
    is certified (conformance harness, ``tests/conformance/``) to land
    within ``fp_margin(D, scale)`` of the raw front-door value — the
    batched GEMM's shape differs from the raw one's, so agreement is
    margin-pinned, NOT bitwise.  Lane results are invariant to batch size
    and composition (also conformance-pinned), so the cascade's bounds
    never depend on which candidates happened to survive together.

    ``backend`` names any registered masked backend; the batched-native
    ones (``batched_pallas``/``batched_mirror``) run the slab as ONE
    launch and honour the per-set prune gate ``gate_lb``/``gate_cut`` —
    gated-out lanes (certified ``lb > cut``, plus the pow2 batch-padding
    duplicates the cascade feeds in with ``lb = +inf``) return the +inf
    sentinel.  Only the Pallas kernel skips a gated lane's GEMMs
    in-kernel (``pl.when``); the pure-JAX routes compute every lane and
    apply the gate as a lane select (shape-static vmap cannot drop work).
    """
    with jax.named_scope("cascade.stage2_batch"):
        return masked.masked_exact_hd_batched(
            q, pts, valid_slab=valid, lb=gate_lb, cut=gate_cut,
            directed=directed, backend=backend, block_a=block_a, block_b=block_b,
        )


# Stage 1 holds a few (n_q + cap, D) fp32 temporaries per lane (the centred
# concat, its Gram matrix, the eigh workspace): compiled for a v5e at
# n_q = 32, cap = 128, D = 128, a 32768-lane launch needs 9.1 GB of
# temporaries next to the resident corpus.  One launch takes at most this
# many (n_q + cap) · D elements summed over its lanes (~1.1 GB there).
_STAGE1_LAUNCH_ELEMS = 1 << 27


def _stage1_certs(q, bucket, rows, *, alpha, m, directed, backend, shard_ctx=None):
    """Masked ProHD certificates of ``rows`` of one bucket, as numpy
    fields of length ``rows.size``, in launches of at most
    ``_STAGE1_LAUNCH_ELEMS`` elements: a power of two of lanes, with a
    short last chunk padded to the full width so that a chunked bucket
    compiles one shape."""
    per_lane = (int(q.shape[0]) + bucket.capacity) * int(q.shape[1])
    lanes = 1 << max(0, (_STAGE1_LAUNCH_ELEMS // per_lane).bit_length() - 1)
    parts = []
    for lo in range(0, rows.size, lanes):
        chunk = rows[lo:lo + lanes]
        if shard_ctx is not None:
            from repro.index import sharded as _sharded  # lazy: avoids cycle

            cert = _sharded.stage1_certs(
                shard_ctx, q, bucket, chunk,
                alpha=alpha, m=m, directed=directed, backend=backend,
            )
        else:
            take = _pow2_take(chunk, lanes if rows.size > lanes else None)
            cert = _stage1_batch(
                q,
                jnp.take(bucket.points, take, axis=0),
                jnp.take(bucket.valid, take, axis=0),
                alpha=alpha, m=m, directed=directed, backend=backend,
            )
        parts.append([np.asarray(f)[: chunk.size] for f in cert])
    return type(cert)(*(np.concatenate(f) for f in zip(*parts)))


def _kth_smallest(ub: np.ndarray, k: int) -> float:
    return float(np.partition(ub, k - 1)[k - 1])


def _pow2_take(rows: np.ndarray, width: int | None = None) -> jnp.ndarray:
    """Gather indices padded to a power of two (or to ``width``) by
    repeating row 0 — THE jit-cache discipline for every batched slab
    gather (stage 1 and stage 2a share it); callers slice results back to
    ``rows.size``."""
    pad = (bucket_capacity(rows.size, 1) if width is None else width) - rows.size
    return jnp.asarray(np.concatenate([rows, np.full((pad,), rows[0])]))


def _rank(values: np.ndarray, candidates: np.ndarray, k: int) -> np.ndarray:
    """k candidate ids, ascending by (value, id) — the deterministic
    tie-break shared with the brute-force reference."""
    order = np.lexsort((candidates, values[candidates]))
    return candidates[order[:k]]


def anytime_frontier(lb, ub, resolved, k: int, epsilon: float):
    """The ε-convergence rule of ``mode="anytime"`` — pure numpy, shared by
    the single-query cascade and the multi-query batch path so the two can
    never diverge on what "converged" means.

    Returns ``(frontier_mask, top, tau)``:

    top      — the current top-k candidate ids, ascending by (certified
               upper bound, id); the membership an anytime return reports.
    tau      — the k-th smallest certified upper bound (``ub[top[-1]]``).
    frontier — boolean (n,) mask of the candidates whose refinement the
               ε-stability of that top-k still requires, the union of two
               blocker classes:

               * value-precision blockers — unresolved members whose
                 interval is wider than ε (so every RETURNED interval ends
                 up ≤ ε wide, or exact);
               * membership blockers — unresolved non-members with
                 ``lb ≤ τ − ε``, i.e. candidates that could still beat the
                 reported top-k by MORE than ε.

    An empty frontier certifies the ε-approximate top-k guarantee: every
    excluded candidate's true distance exceeds ``τ − ε``, and every
    included one's is at most ``τ`` — no excluded candidate beats an
    included one by more than ε.  At ε = 0 the rule degenerates to the
    exact cascade's drain frontier (members must resolve exactly, and
    every candidate with ``lb ≤ τ`` blocks), which is why a fully drained
    ε = 0 anytime search returns brute force's bits.
    """
    n = int(lb.shape[0])
    order = np.lexsort((np.arange(n), ub))
    top = order[:k]
    tau = float(ub[top[-1]])
    in_top = np.zeros((n,), bool)
    in_top[top] = True
    unresolved = ~np.asarray(resolved, bool)
    # Tombstoned candidates carry lb = ub = +inf whose width is inf − inf
    # = nan; they can never be in the top (k ≤ n_live) nor block it
    # (lb = +inf exceeds every finite τ − ε), so the nan is always masked
    # out — silence only the IEEE invalid-op warning it would emit.
    with np.errstate(invalid="ignore"):
        width_blockers = in_top & unresolved & ((ub - lb) > epsilon)
    member_blockers = ~in_top & unresolved & (lb <= tau - epsilon)
    return width_blockers | member_blockers, top, tau


def certified_recall(lb, ub, top, k: int) -> float:
    """Fraction of ``top`` PROVABLY in the exact top-k, from intervals alone.

    Hit ``i`` is certified in SOME valid top-k iff at most k−1 other
    candidates can STRICTLY beat it.  ``j`` can strictly beat ``i`` only
    if ``lb_j < ub_i`` (otherwise ``value_j ≥ lb_j ≥ ub_i ≥ value_i``), so
    counting ``lb_j < ub_i`` upper-bounds the strict beaters — the strict
    inequality is what keeps exactly-tied candidates (duplicate sets:
    ``lb_j = ub_i`` once resolved) from pessimising the certificate, since
    a tie is resolvable in ``i``'s favour under a (value, id) tie-break.
    The rule is monotone — tightening any interval can only certify more
    hits — which is what makes the reported recall sound to act on: it
    never overestimates the true recall (conformance-gated).
    """
    if k <= 0:
        return 1.0
    top = np.asarray(top)
    ub_top = np.asarray(ub)[top]
    counts = (np.asarray(lb)[None, :] < ub_top[:, None]).sum(axis=1)
    # an unresolved candidate counts itself (lb_i < ub_i): never a strict
    # beater of itself, so subtract it back out
    counts -= (np.asarray(lb)[top] < ub_top).astype(counts.dtype)
    return float(int((counts <= k - 1).sum()) / k)


def _exact_value(query, pts, variant: str, backend: str, cfg: HDConfig) -> np.float32:
    from repro import hd as _hd

    res = _hd.set_distance(
        query, pts, variant=variant, method="exact", backend=backend, config=cfg
    )
    return np.float32(res.value)


def search(
    query,
    store: SetStore,
    k: int,
    *,
    variant: str = "hausdorff",
    method: str = "cascade",
    backend: str = "auto",
    stage2: str = "batched",
    masked_backend: str | None = None,
    config: HDConfig | None = None,
    measure: bool = False,
    deadline_s: float | None = None,
    on_fault: str = "degrade",
    validate: bool = True,
    mode: str = "exact",
    epsilon: float = 0.0,
    budget: int | None = None,
    shards: int | None = None,
) -> SearchResult:
    # Observability shim: when tracing is off this is ONE flag check on top
    # of the implementation; when on, the whole request runs under a root
    # "index.search" span (fresh rid unless an engine/server frame is
    # ambient) with the cascade stages as children.
    kwargs = dict(
        variant=variant, method=method, backend=backend, stage2=stage2,
        masked_backend=masked_backend, config=config, measure=measure,
        deadline_s=deadline_s, on_fault=on_fault, validate=validate,
        mode=mode, epsilon=epsilon, budget=budget, shards=shards,
    )
    if not _obs.enabled():
        return _search_impl(query, store, k, **kwargs)
    with _obs.span(
        "index.search", k=k, variant=variant, method=method, stage2=stage2,
        mode=mode, shards=shards,
    ) as sp:
        res = _search_impl(query, store, k, **kwargs)
        sp.set(
            degraded=res.degraded,
            stage_reached=res.stage_reached,
            exact_refines=res.stats.get("exact_refines", 0),
            prune_fraction=res.stats.get("prune_fraction"),
            certified_recall=res.certified_recall_at_k,
        )
        _record_stats("index.search", res.stats)
        return res


def _search_impl(
    query,
    store: SetStore,
    k: int,
    *,
    variant: str = "hausdorff",
    method: str = "cascade",
    backend: str = "auto",
    stage2: str = "batched",
    masked_backend: str | None = None,
    config: HDConfig | None = None,
    measure: bool = False,
    deadline_s: float | None = None,
    on_fault: str = "degrade",
    validate: bool = True,
    mode: str = "exact",
    epsilon: float = 0.0,
    budget: int | None = None,
    shards: int | None = None,
) -> SearchResult:
    """Top-k nearest stored sets to ``query`` under a set distance.

    query    — (n_q, D) points, n_q ≥ 1 (HD is undefined on empty sets)
    store    — the SetStore to search
    k        — how many neighbours (k ≥ corpus size returns the full
               ranking; k == 0 returns an empty result without touching
               the corpus)
    variant  — hausdorff | directed (h(query → set))
    method   — cascade (certified bound cascade) | exact (brute force —
               every set refined; the reference the cascade provably
               matches)
    backend  — backend for the exact refines (``repro.hd`` names; "auto")
    stage2   — batched (one vmapped masked exact pass per surviving
               bucket tightens every interval to ±fp_margin, then only the
               ≈ k boundary candidates are raw-refined) | sequential (the
               legacy per-candidate front-door loop over the whole
               frontier).  Both return identical bits; batched keeps the
               stage-2 jit cache at O(distinct bucket shapes) + O(k)
               instead of O(frontier).
    masked_backend — which ``repro.core.masked.EXACT_MASKED_BACKENDS``
               reduction serves the bucket-granularity passes (stage-1
               certificates and the stage-2a batched tightening).  None
               (default) resolves like ``backend="auto"``: the batched
               bucket kernel where Pallas is native (TPU), its pure-JAX
               batched mirror elsewhere — never interpret-mode Pallas.
               Any registered name is valid; the returned top-k is
               identical under every one of them (conformance-gated).
    config   — HDConfig; ``alpha`` drives the stage-1 masked ProHD
    deadline_s — wall-clock budget for THIS search.  None (default) is
               unbounded.  On expiry the cascade stops escalating and
               returns the best certified state reached as a DEGRADED
               result (``degraded=True``; see :class:`SearchResult`) —
               stage-0 intervals at worst, partially stage-2-refined at
               best.  Stage 0 always runs (it is the cheapest certified
               state and the floor of the degradation ladder).
    on_fault — "degrade" (default): a runtime fault in stages 1+ (typed
               reliability fault, XLA/device RuntimeError, FP error) is
               absorbed and the best certified state is returned degraded,
               with the fault recorded in ``stats['fault']``; "raise"
               propagates it.  Stage-0 faults always raise — before stage
               0 there is no certified state to degrade to.  Programming
               errors (ValueError/TypeError) always propagate.
    validate — reject non-finite query coordinates (NaN/Inf) with a
               ValueError; they would silently poison every certified
               bound.  ``validate=False`` is the pre-validated hot-path
               escape hatch.
    mode     — "exact" (default): the cascade drains to the provably
               brute-force-identical top-k.  "anytime": the recall/latency
               knob (docs/api.md, "Anytime search contract") — the cascade
               keeps per-candidate ProHD point estimates with certified
               [lb, ub] intervals, escalates stages only for the
               candidates the ε-stability of the top-k still requires
               (:func:`anytime_frontier`), refines greedily
               tightest-first (ascending certified lower bound), and
               stops as soon as no excluded candidate can beat an
               included one by more than ``epsilon`` AND every returned
               interval is ≤ ε wide (or exact).  The result reports
               ``certified_recall_at_k`` and the ladder rung reached in
               ``stage_reached``.  With ε = 0 and no budget, anytime
               degenerates BIT-FOR-BIT to the exact cascade
               (conformance-gated under every masked backend).
    epsilon  — anytime only: the absolute distance tolerance (same units
               as the returned values).  ε ≥ 0; larger ε terminates
               earlier (ε above the corpus diameter returns the certified
               stage-0 state untouched).
    budget   — anytime only: cap on raw exact refines the anytime drain
               may spend (None = unbounded).  Exhausting it stops the
               ladder with ``stats['converged'] = False`` — a budget stop
               is an honest partial answer, NOT a degraded one (degraded
               stays reserved for deadlines and absorbed faults).
               Refinement order is deterministic, so a larger budget's
               refine sequence extends a smaller one's: intervals only
               tighten and certified recall never decreases as the budget
               grows (property-gated).
    shards   — corpus-parallel stage 0/1 over the first ``shards`` visible
               devices (``repro.index.sharded``): summaries split
               row-wise, bucket frontier lanes round-robin by slot, then
               a cross-shard certified top-k merge re-applies the global
               prune rule before the unchanged stage-2 raw refinement —
               the sharded top-k is bit-for-bit the single-device result
               (gated in scripts/check.sh under 8 forced host devices).
               None (default) runs in-process; 1 exercises the sharded
               path on a one-device mesh.  Exact cascade only for now
               (``mode="anytime"`` and ``method="exact"`` reject it).

    Tombstoned (deleted/updated-away) sets are certified out, never
    ranked: their intervals are pinned to [+inf, +inf] after stage 0, the
    packed-slab gates return the +inf sentinel for their slots, and
    ``k_eff = min(k, store.n_live)`` — a search over a store with no live
    sets raises ValueError like the empty store.

    Returns a :class:`SearchResult`; unless ``degraded`` is set, the top-k
    ids and values are identical to brute force by construction (see
    module docstring) for ``mode="exact"``, and carry the ε certificate
    above for ``mode="anytime"``.
    """
    if variant not in SEARCH_VARIANTS:
        raise ValueError(f"unknown search variant {variant!r}; expected one of {SEARCH_VARIANTS}")
    if method not in SEARCH_METHODS:
        raise ValueError(f"unknown search method {method!r}; expected one of {SEARCH_METHODS}")
    if stage2 not in STAGE2_MODES:
        raise ValueError(f"unknown stage2 mode {stage2!r}; expected one of {STAGE2_MODES}")
    if on_fault not in ON_FAULT_MODES:
        raise ValueError(f"unknown on_fault mode {on_fault!r}; expected one of {ON_FAULT_MODES}")
    if mode not in SEARCH_MODES:
        raise ValueError(f"unknown search mode {mode!r}; expected one of {SEARCH_MODES}")
    epsilon = float(epsilon)
    if not np.isfinite(epsilon) or epsilon < 0.0:
        raise ValueError(f"epsilon must be a finite float >= 0, got {epsilon}")
    if budget is not None and int(budget) < 0:
        raise ValueError(f"budget must be None or an int >= 0, got {budget}")
    if mode == "exact" and (epsilon != 0.0 or budget is not None):
        raise ValueError(
            "epsilon/budget are anytime knobs; pass mode='anytime' to use them"
        )
    if mode == "anytime" and method == "exact":
        raise ValueError(
            "mode='anytime' rides the certified cascade; method='exact' "
            "(brute force) has no bounds to refine — drop one of the two"
        )
    # ε = 0 with no budget is DEFINED as the exact cascade (the knob's
    # degenerate endpoint): run the exact code path, so bit-for-bit
    # identity is structural, not an equivalence to maintain.
    anytime = mode == "anytime" and (epsilon > 0.0 or budget is not None)
    if k < 0:
        raise ValueError(f"k must be >= 0, got {k}")
    if masked_backend is not None and masked_backend not in masked.EXACT_MASKED_BACKENDS:
        raise ValueError(
            f"unknown masked backend {masked_backend!r}; expected one of "
            f"{tuple(sorted(masked.EXACT_MASKED_BACKENDS))}"
        )
    if store.n_sets == 0:
        raise ValueError("cannot search an empty SetStore")
    live = store.live_mask()
    n_live = int(live.sum())
    if n_live == 0:
        raise ValueError(
            "cannot search a SetStore with no live sets (every set was "
            "deleted); add sets or restore a snapshot first"
        )
    if shards is not None:
        if mode == "anytime":
            raise ValueError(
                "shards= is not yet supported with mode='anytime' (see "
                "ROADMAP: anytime through the sharded path) — drop one of "
                "the two"
            )
        if method == "exact":
            raise ValueError(
                "shards= parallelises the cascade's stage 0/1; "
                "method='exact' (brute force) has no such stages — drop "
                "one of the two"
            )
    cfg = config if config is not None else HDConfig()
    q = jnp.asarray(query, jnp.float32)
    if q.ndim != 2 or q.shape[1] != store.dim:
        raise ValueError(f"expected (n_q, {store.dim}) query, got shape {q.shape}")
    if q.shape[0] < 1:
        raise ValueError("query must contain at least one point (HD is undefined on empty sets)")
    if validate and not bool(np.isfinite(np.asarray(q)).all()):
        raise ValueError(
            "query contains non-finite coordinates (NaN/Inf); certified "
            "bounds are undefined over them — clean the query or pass "
            "validate=False"
        )
    if k == 0:
        # Well-defined degenerate request: nothing asked for, nothing done.
        meta = HDMeta(
            variant=variant, method=method, backend=backend,
            block_a=0, block_b=0, elapsed_s=0.0 if measure else None,
            mode=mode,
        )
        stats0: dict[str, Any] = {
            "candidates_scanned": store.n_sets, "k": 0,
            "stage0_pruned": 0, "stage1_pruned": 0,
            "stage2_mode": stage2, "stage2_calls": 0,
            "stage2_distinct_shapes": 0, "stage2_batched_candidates": 0,
            "exact_refines": 0, "prune_fraction": 1.0, "mode": mode,
        }
        if mode == "anytime":
            stats0.update(epsilon=epsilon, budget=budget,
                          anytime_refines=0, converged=True)
        return SearchResult(
            ids=np.zeros((0,), np.int32),
            values=np.zeros((0,), np.float32),
            stats=stats0,
            meta=meta,
        )

    t0 = _now() if measure else 0.0
    budget = None if budget is None else int(budget)
    deadline = _Budget(deadline_s)
    n = store.n_sets
    # Tombstoned sets are certified non-candidates: rank depth follows the
    # LIVE count, and their intervals are pinned to +inf after stage 0.
    k_eff = min(k, n_live)
    has_dead = n_live < n
    dead = ~live if has_dead else None
    directed = variant == "directed"
    device_kind = resolver.default_device_kind()
    shard_ctx = None
    if shards is not None:
        from repro.index import sharded as _sharded  # lazy: avoids cycle
        shard_ctx = _sharded.make_shard_context(shards)
    mb = masked_backend or resolver.resolve_masked_backend(
        int(q.shape[0]), 0, store.dim, device_kind=device_kind
    )
    # Masked-backend fallback ladder: the requested/resolved backend first,
    # then every other registered one (interpret-only *_pallas backends are
    # excluded off-TPU, matching the resolver).  A BackendUnavailable from
    # any bucket-granularity dispatch permanently advances the ladder —
    # every registered backend is conformance-certified, so the top-k is
    # identical whichever one ends up serving.
    available = [mb] + [
        b for b in sorted(masked.EXACT_MASKED_BACKENDS)
        if b != mb and (device_kind == "tpu" or not b.endswith("_pallas"))
    ]
    backend_fallbacks: list[str] = []
    # Stage-2b refines share ONE dispatch decision per search: "auto" used
    # to re-enter resolver.resolve_backend through the front door once per
    # candidate inside the drain loop.  Resolve it here against the
    # corpus's dominant (largest) set shape and thread the concrete name
    # through every refine; passing a concrete backend to set_distance
    # skips its own resolution, so the resolver runs exactly once.
    refine_backend = backend
    if backend == "auto":
        refine_backend = resolver.resolve_backend(
            variant, "exact", int(q.shape[0]), int(store.counts().max()),
            store.dim, device_kind=device_kind,
        )
    _obs.event(
        "cascade.backend_resolved", masked_backend=mb,
        refine_backend=refine_backend, device_kind=device_kind,
    )

    def _with_backend(call):
        """call(backend) under the fallback ladder; returns its result."""
        while True:
            be = available[0]
            try:
                _faults.fire(_POINT_BACKEND, backend=be)
                return call(be)
            except BackendUnavailable:
                backend_fallbacks.append(be)
                available.pop(0)
                _obs.event(
                    "cascade.backend_fallback", failed=be,
                    next=available[0] if available else None,
                )
                if not available:
                    raise

    values = np.full((n,), np.inf, np.float32)
    resolved = np.zeros((n,), bool)
    # Certified per-candidate interval state — the degradation ladder's
    # collateral.  Vacuous-but-sound [0, +inf) until a stage tightens it,
    # so a degraded return is certified at EVERY point of the cascade.
    lb = np.zeros((n,), np.float64)
    ub = np.full((n,), np.inf, np.float64)
    # Anytime point estimates (float64, NaN until a stage produces one):
    # stage 1 contributes the masked ProHD value, stage 2a the batched
    # exact value, stage 2b the raw exact value.  Consulted only by the
    # anytime assembly, and always clipped into the certified interval.
    est = np.full((n,), np.nan, np.float64)
    anytime_refines = 0
    anytime_converged = False
    exact_refines = 0
    degraded = False
    stage_reached = "stage0"
    fault: BaseException | None = None
    stats: dict[str, Any] = {"candidates_scanned": n, "n_live": n_live, "k": k_eff}
    if shard_ctx is not None:
        stats["shards"] = shard_ctx.n_shards

    def checkpoint() -> None:
        if deadline.expired():
            raise _DeadlineHit()

    def refine(sid: int) -> None:
        nonlocal exact_refines
        values[sid] = _exact_value(q, store.get(sid), variant, refine_backend, cfg)
        resolved[sid] = True
        exact_refines += 1

    if method == "exact":
        stats.update(stage0_pruned=0, stage1_pruned=0)
        try:
            _faults.fire(_POINT_STAGE2B)
            for sid in range(n):
                if has_dead and not live[sid]:
                    continue  # brute force over the SURVIVORS only
                checkpoint()
                refine(sid)
                lb[sid] = ub[sid] = float(values[sid])
            stage_reached = "stage2b"
        except _DeadlineHit:
            degraded = True
            stage_reached = "stage2b" if exact_refines else "stage0"
        except _DEGRADABLE as e:
            if on_fault == "raise":
                raise
            degraded = True
            fault = e
            stage_reached = "stage2b" if exact_refines else "stage0"
    else:
        # -- stage 0: summary bounds over the whole corpus, one shot ------
        # Always runs, deadline or not: it is the cheapest certified state
        # and the floor of the degradation ladder.  A failure HERE has no
        # certified state to fall back to, so it propagates (typed).
        with _obs.span("cascade.stage0", n=n) as _sp0:
            _faults.fire(_POINT_STAGE0)
            qsum = store.summarize(q)
            if shard_ctx is not None:
                # Corpus rows split across the mesh; per-row bound math is
                # row-local, so the gathered bits match the in-process
                # path's row for row.
                lo64, hi64, scale = _sharded.stage0_bounds(
                    shard_ctx, qsum, store.summaries(), directed=directed,
                )
                lb, ub = certified_margins(lo64, hi64, scale, store.dim)
                _sp0.set(shards=shard_ctx.n_shards)
            else:
                lb_j, ub_j = _interval_bounds_jit(qsum, store.summaries(), directed=directed)
                scale = np.asarray(_bound_scale_jit(qsum, store.summaries()), np.float64)
                lb_j, ub_j = certified_margins(lb_j, ub_j, jnp.asarray(scale), store.dim)
                lb = np.asarray(lb_j, np.float64)
                ub = np.asarray(ub_j, np.float64)
            if has_dead:
                # Tombstoned sets: stale summary rows may still sit in the
                # stacked summaries — pin their intervals to the certified
                # +inf sentinel so no stage ranks, gates or refines them.
                lb[dead] = np.inf
                ub[dead] = np.inf

            tau = _kth_smallest(ub, k_eff)
            alive = lb <= tau
            stats["stage0_pruned"] = int(n - alive.sum())
            stats["stage1_pruned"] = 0
            _sp0.set(pruned=stats["stage0_pruned"])

        # Work accounting (see stage-2 comment below); initialized before
        # the degradable region so a degraded return still reports it.
        stage2_shapes: set[tuple] = set()
        stage2_calls = 0
        stats["stage2_batched_candidates"] = 0   # frontier measured by 2a

        def drain_raw() -> None:
            """Raw front-door resolution, ascending lower bound, until the
            frontier is empty — the WHOLE of sequential mode, and stage 2b
            of batched mode (one shared loop so the modes cannot diverge)."""
            nonlocal alive, stage2_calls, stage_reached
            with _obs.span("cascade.stage2b") as _sp2b:
                _faults.fire(_POINT_STAGE2B)
                refines = 0
                while True:
                    tau = _kth_smallest(ub, k_eff)
                    alive &= lb <= tau
                    frontier = np.nonzero(alive & ~resolved)[0]
                    if frontier.size == 0:
                        _sp2b.set(refines=refines)
                        return
                    checkpoint()
                    sid = int(frontier[np.lexsort((frontier, lb[frontier]))[0]])
                    refine(sid)
                    stage2_shapes.add((store.get(sid).shape[0],))
                    stage2_calls += 1
                    refines += 1
                    lb[sid] = ub[sid] = float(values[sid])
                    stage_reached = "stage2b"

        def run_anytime() -> None:
            """The anytime escalation ladder (``mode="anytime"`` with ε > 0
            or a refine budget): drive the SAME certified stages the exact
            cascade uses, but only over the candidates the ε-stability of
            the top-k still requires (:func:`anytime_frontier`), and stop
            the moment the frontier empties — or the refine budget runs
            out (an honest partial answer: ``converged=False``, never
            degraded).  Every interval update is identical to the exact
            cascade's, so deadline/fault degradation needs no
            anytime-specific handling — the shared except clauses return
            the best certified state exactly as they do for exact mode."""
            nonlocal stage_reached, anytime_refines, anytime_converged
            nonlocal stage2_calls
            with _obs.span(
                "cascade.anytime", epsilon=epsilon,
                budget=-1 if budget is None else budget, k=k_eff,
            ) as _spany:
                _faults.fire(_POINT_ANYTIME)
                cap_refines = resolver.resolve_anytime_refine_cap(
                    n, k_eff, budget
                )
                front, _, _ = anytime_frontier(lb, ub, resolved, k_eff, epsilon)
                stage0_front = int(front.sum())

                # -- stage 1: masked ProHD certificates, frontier rows only
                if front.any():
                    checkpoint()
                    _faults.fire(_POINT_STAGE1)
                    m = projections.default_num_directions(store.dim)
                    for bucket in store.packed_buckets().values():
                        # & bucket.live: an updated set's OLD (tombstoned)
                        # slot certifies +inf — gathering it would falsely
                        # prune the live set (see PackedBucket docstring).
                        rows = np.nonzero(front[bucket.set_ids] & bucket.live)[0]
                        if rows.size == 0:
                            continue
                        checkpoint()
                        cert = _with_backend(lambda be: _stage1_certs(
                            q, bucket, rows,
                            alpha=cfg.alpha, m=m, directed=directed, backend=be,
                        ))
                        lo1 = np.maximum(cert.hd, cert.lower)
                        sids = bucket.set_ids[rows]
                        lb1, ub1 = certified_margins(
                            lo1.astype(np.float64),
                            cert.upper.astype(np.float64),
                            scale[sids],
                            store.dim,
                        )
                        lb[sids] = np.maximum(lb[sids], lb1)
                        ub[sids] = np.minimum(ub[sids], ub1)
                        est[sids] = np.clip(
                            np.asarray(cert.hd, np.float64)[: rows.size],
                            lb[sids], ub[sids],
                        )
                        stage_reached = "stage1"
                    front, _, _ = anytime_frontier(lb, ub, resolved, k_eff, epsilon)

                # -- stage 2a: batched masked EXACT, frontier rows only ----
                if front.any():
                    checkpoint()
                    _faults.fire(_POINT_STAGE2A)
                    slot = store.slot_index()
                    buckets = store.packed_buckets()
                    n_q = int(q.shape[0])
                    groups: dict[int, list[int]] = {}
                    for sid in np.nonzero(front)[0]:
                        groups.setdefault(slot[int(sid)][0], []).append(int(sid))
                    for cap in sorted(groups, key=lambda c: min(lb[s] for s in groups[c])):
                        # One bucket's tightened intervals shrink the next
                        # bucket's frontier — the exact 2a loop's
                        # adaptivity, under the ε-frontier rule.  Every
                        # frontier member provably has lb ≤ τ (top members
                        # by ub ≤ τ, outside blockers by lb ≤ τ − ε), so
                        # the in-kernel lb/cut gate can never skip a lane
                        # we need.
                        front2, _, tau = anytime_frontier(
                            lb, ub, resolved, k_eff, epsilon
                        )
                        sids = [s for s in groups[cap] if front2[s]]
                        if not sids:
                            continue
                        checkpoint()
                        stats["stage2_batched_candidates"] += len(sids)
                        bucket = buckets[cap]
                        rows = np.asarray([slot[s][1] for s in sids])
                        take = _pow2_take(rows)
                        batch = int(take.shape[0])
                        gate_lb = np.concatenate(
                            [lb[sids], np.full((batch - rows.size,), np.inf)]
                        ).astype(np.float32)
                        gate_cut = np.full(
                            (batch,),
                            tau * (1.0 + 1e-6) if np.isfinite(tau) else np.inf,
                            np.float32,
                        )

                        def _call_2a(be):
                            block_a, block_b = resolver.resolve_block_sizes(
                                n_q, cap, store.dim, device_kind=device_kind,
                                backend="fused_pallas" if be == "batched_pallas" else "tiled",
                            )
                            return be, _stage2_batch(
                                q,
                                jnp.take(bucket.points, take, axis=0),
                                jnp.take(bucket.valid, take, axis=0),
                                jnp.asarray(gate_lb),
                                jnp.asarray(gate_cut),
                                directed=directed, backend=be,
                                block_a=block_a, block_b=block_b,
                            )

                        used_be, raw_vals = _with_backend(_call_2a)
                        vals = np.asarray(raw_vals, np.float64)[: rows.size]
                        pad = fp_value_margin(store.dim, scale[sids], vals)
                        lb[sids] = np.maximum(lb[sids], np.maximum(vals - pad, 0.0))
                        ub[sids] = np.minimum(ub[sids], vals + pad)
                        est[sids] = np.clip(vals, lb[sids], ub[sids])
                        stage2_shapes.add((cap, batch, used_be))
                        stage2_calls += 1
                        stage_reached = "stage2a"
                    front, _, _ = anytime_frontier(lb, ub, resolved, k_eff, epsilon)

                # -- stage 2b: greedy raw refinement, tightest-first -------
                # Ascending certified lower bound (tie: id) — Chubet-style
                # greedy order: the candidate most likely to decide the
                # top-k boundary is refined first.  Deterministic, so a
                # larger budget's refine sequence extends a smaller one's.
                if front.any() and cap_refines > 0:
                    _faults.fire(_POINT_STAGE2B)
                while front.any() and anytime_refines < cap_refines:
                    checkpoint()
                    cand = np.nonzero(front)[0]
                    sid = int(cand[np.lexsort((cand, lb[cand]))[0]])
                    refine(sid)
                    lb[sid] = ub[sid] = est[sid] = float(values[sid])
                    anytime_refines += 1
                    stage_reached = "stage2b"
                    front, _, _ = anytime_frontier(lb, ub, resolved, k_eff, epsilon)
                anytime_converged = not bool(front.any())
                _spany.set(
                    refines=anytime_refines, converged=anytime_converged,
                    stage0_frontier=stage0_front,
                    frontier_left=int(front.sum()),
                )

        try:
            # -- stage 1: vmapped bucketed masked ProHD on the survivors --
            # (exact mode; the anytime ladder runs its own frontier-
            # restricted stage 1 inside ``run_anytime``)
            if not anytime and int(alive.sum()) > k_eff:
                with _obs.span("cascade.stage1", frontier=int(alive.sum())) as _sp1:
                    checkpoint()
                    _faults.fire(_POINT_STAGE1)
                    m = projections.default_num_directions(store.dim)
                    for bucket in store.packed_buckets().values():
                        # ``& bucket.live``: an UPDATED set is alive but its
                        # OLD slot is a tombstone whose masked certificate
                        # is the +inf sentinel — folding that lb in would
                        # falsely prune the live set (see PackedBucket).
                        rows = np.nonzero(alive[bucket.set_ids] & bucket.live)[0]
                        if rows.size == 0:
                            continue
                        checkpoint()
                        cert = _with_backend(lambda be: _stage1_certs(
                            q, bucket, rows,
                            alpha=cfg.alpha, m=m, directed=directed, backend=be,
                            shard_ctx=shard_ctx,
                        ))
                        lo1 = np.maximum(cert.hd, cert.lower)
                        sids = bucket.set_ids[rows]
                        lb1, ub1 = certified_margins(
                            lo1.astype(np.float64),
                            cert.upper.astype(np.float64),
                            scale[sids],
                            store.dim,
                        )
                        lb[sids] = np.maximum(lb[sids], lb1)
                        ub[sids] = np.minimum(ub[sids], ub1)
                        stage_reached = "stage1"
                    if shard_ctx is not None:
                        # Cross-shard certified top-k merge: the per-shard
                        # certificates are already folded into the global
                        # interval state; re-apply the prune rule
                        # ``lb > k-th smallest certified ub`` over the
                        # whole corpus before the unchanged stage 2.
                        with _obs.span(
                            "cascade.shard_merge", shards=shard_ctx.n_shards,
                        ) as _spm:
                            tau, still = _sharded.merge_topk(lb, ub, alive, k_eff)
                            _spm.set(pruned=int(alive.sum() - still.sum()))
                    else:
                        tau = _kth_smallest(ub, k_eff)
                        still = alive & (lb <= tau)
                    stats["stage1_pruned"] = int(alive.sum() - still.sum())
                    alive = still
                    _sp1.set(pruned=stats["stage1_pruned"])

            # -- stage 2: exact refinement of the frontier ----------------
            # Both modes drain the frontier under the same certified prune
            # rule; they differ only in dispatch granularity.  Work
            # accounting: ``stage2_calls`` counts jitted refinement
            # dispatches and ``stage2_shapes`` the distinct jit-cache keys
            # they exercise — sequential pays one call per frontier
            # candidate and one cache entry per distinct RAW set shape;
            # batched pays one masked pass per surviving bucket (cache
            # key: capacity × padded batch × family) plus one raw call per
            # boundary candidate (≈ k).
            if anytime:
                # The ε/budget escalation ladder replaces stage 1 + stage 2
                # wholesale (defined above, next to drain_raw).
                run_anytime()
            elif stage2 == "sequential":
                drain_raw()
            else:
                # -- 2a: one vmapped masked EXACT pass per surviving
                # bucket.  The padded value is certified to land within
                # fp_margin of the raw front-door value (both err
                # ≤ sqrt((D+2)·eps)·scale from the true distance; GEMM
                # bits legitimately differ across padded shapes — the
                # conformance harness pins the margin), so every frontier
                # interval collapses to ±fp_margin without a single
                # per-candidate dispatch.  Final values still come from
                # stage 2b's raw refines, so batching cannot perturb a bit
                # of the output.
                with _obs.span("cascade.stage2a") as _sp2a:
                    checkpoint()
                    _faults.fire(_POINT_STAGE2A)
                    slot = store.slot_index()
                    buckets = store.packed_buckets()
                    n_q = int(q.shape[0])
                    tau = _kth_smallest(ub, k_eff)
                    alive &= lb <= tau
                    frontier = np.nonzero(alive & ~resolved)[0]
                    groups: dict[int, list[int]] = {}
                    for sid in frontier:
                        groups.setdefault(slot[int(sid)][0], []).append(int(sid))
                    # Ascending best-lower-bound bucket order, re-deriving τ
                    # between buckets: one bucket's tight intervals prune the
                    # next bucket's stragglers, preserving the sequential
                    # loop's adaptivity at batch granularity.
                    for cap in sorted(groups, key=lambda c: min(lb[s] for s in groups[c])):
                        tau = _kth_smallest(ub, k_eff)
                        sids = [s for s in groups[cap] if lb[s] <= tau]
                        if not sids:
                            continue
                        checkpoint()
                        stats["stage2_batched_candidates"] += len(sids)
                        bucket = buckets[cap]
                        rows = np.asarray([slot[s][1] for s in sids])
                        take = _pow2_take(rows)
                        batch = int(take.shape[0])
                        # Per-set prune gate: every real lane carries its
                        # certified stage-0/1 lower bound against a cutoff
                        # safely ABOVE τ (1e-6 relative headroom dwarfs the
                        # float32 cast error, so a lane with lb ≤ τ in float64
                        # can never be skipped by the cast — a skip is always
                        # certified lb > τ); the pow2 batch-padding duplicate
                        # lanes ride in with lb = +inf and are gated
                        # unconditionally — which saves their GEMMs in-kernel
                        # on the Pallas route (the pure-JAX routes still
                        # compute them and select the sentinel).
                        gate_lb = np.concatenate(
                            [lb[sids], np.full((batch - rows.size,), np.inf)]
                        ).astype(np.float32)
                        gate_cut = np.full(
                            (batch,),
                            tau * (1.0 + 1e-6) if np.isfinite(tau) else np.inf,
                            np.float32,
                        )

                        def _call_2a(be):
                            block_a, block_b = resolver.resolve_block_sizes(
                                n_q, cap, store.dim, device_kind=device_kind,
                                backend="fused_pallas" if be == "batched_pallas" else "tiled",
                            )
                            return be, block_a, block_b, _stage2_batch(
                                q,
                                jnp.take(bucket.points, take, axis=0),
                                jnp.take(bucket.valid, take, axis=0),
                                jnp.asarray(gate_lb),
                                jnp.asarray(gate_cut),
                                directed=directed, backend=be,
                                block_a=block_a, block_b=block_b,
                            )

                        used_be, _, _, raw_vals = _with_backend(_call_2a)
                        vals = np.asarray(raw_vals, np.float64)[: rows.size]
                        pad = fp_value_margin(store.dim, scale[sids], vals)
                        lb[sids] = np.maximum(lb[sids], np.maximum(vals - pad, 0.0))
                        ub[sids] = np.minimum(ub[sids], vals + pad)
                        stage2_shapes.add((cap, batch, used_be))
                        stage2_calls += 1
                        stage_reached = "stage2a"
                    _sp2a.set(
                        batched_candidates=stats["stage2_batched_candidates"],
                        calls=stage2_calls,
                    )
                # -- 2b: raw exact resolution of whatever still straddles
                # the top-k boundary — after 2a that is ≈ k candidates
                # (+ exact ties), each refined on its RAW points so the
                # returned value is bit-for-bit the brute-force number.
                drain_raw()
        except _DeadlineHit:
            degraded = True
        except _DEGRADABLE as e:
            # an exhausted fallback ladder is not degradable — there is no
            # backend left to serve ANY request; the typed error propagates
            if isinstance(e, BackendUnavailable) and not available:
                raise
            if on_fault == "raise":
                raise
            degraded = True
            fault = e
        stats.update(
            stage2_mode=stage2,
            stage2_calls=stage2_calls,
            stage2_distinct_shapes=len(stage2_shapes),
            masked_backend=available[0] if available else None,
        )

    if backend_fallbacks:
        stats["backend_fallbacks"] = list(backend_fallbacks)
    stats.update(
        exact_refines=exact_refines,
        prune_fraction=1.0 - exact_refines / n,
        refine_backend=refine_backend,
        mode=mode,
    )
    if mode == "anytime":
        stats.update(
            epsilon=epsilon, budget=budget,
            anytime_refines=anytime_refines,
            # ε = 0 with no budget runs the exact path: it converged iff it
            # drained (i.e. was not cut short by a deadline/fault).
            converged=anytime_converged if anytime else not degraded,
        )

    if not degraded and anytime:
        # Anytime membership: the k smallest certified upper bounds
        # (tie: id).  On a converged frontier this is exactly the set the
        # ε-guarantee speaks about — no excluded candidate can beat an
        # included one by more than ε, and every returned interval is
        # ≤ ε wide or exact.  Values are the raw exact number where
        # resolved, else the certified point estimate clipped into
        # [lb, ub] (interval midpoint if no stage produced an estimate);
        # presentation order is ascending (value, id), the exact path's
        # ranking rule.
        order = np.lexsort((np.arange(n), ub))
        top = order[:k_eff]
        pt = np.where(np.isnan(est), 0.5 * (lb + ub), np.clip(est, lb, ub))
        vals64 = np.where(resolved, values.astype(np.float64), pt)
        top = top[np.lexsort((top, vals64[top]))]
        out_values = vals64[top].astype(np.float32)
        out_lower = lb[top].copy()
        out_upper = ub[top].copy()
        stage_final = stage_reached
        recall = certified_recall(lb, ub, top, k_eff)
    elif not degraded:
        top = _rank(values, np.nonzero(resolved)[0], k_eff)
        out_values = values[top]
        out_lower = out_upper = out_values.astype(np.float64)
        stage_final = "complete"
        recall = 1.0
    else:
        # Best certified state reached: rank ALL candidates ascending by
        # certified upper bound (tie: dead-last, then id) — refined
        # candidates carry their exact value as a zero-width interval, the
        # rest their tightest stage bounds.  The dead-last key matters only
        # for method="exact" degraded returns, where unresolved LIVE sets
        # still tie tombstoned ones at ub = +inf and must win the tie.
        # Every returned interval provably contains its true distance; the
        # conservative ``values`` entry for an unrefined candidate is its
        # certified upper bound.
        order = np.lexsort((
            np.arange(n), dead if has_dead else np.zeros((n,), bool), ub,
        ))
        top = order[:k_eff]
        out_values = np.where(
            resolved[top], values[top], ub[top].astype(np.float32)
        ).astype(np.float32)
        out_lower = lb[top].copy()
        out_upper = ub[top].copy()
        stage_final = stage_reached
        stats["n_resolved"] = int(resolved.sum())
        stats["deadline_s"] = deadline_s
        # Honest recall certificate for the degraded prefix: how many of
        # the returned hits are PROVABLY top-k under the intervals reached.
        # Vacuous stage-0-of-nothing state certifies 0 of them — correct.
        recall = certified_recall(lb, ub, top, k_eff)
        if fault is not None:
            # Structured: the full __cause__ chain, outermost first — a
            # wrapped root cause survives into logs and span events (the
            # historical one-string flattening lost it).
            stats["fault"] = _obs.exception_chain(fault)
            _obs.event(
                "cascade.fault", error=True,
                stage=stage_reached, chain=stats["fault"],
            )

    elapsed = _now() - t0 if measure else None
    meta = HDMeta(
        variant=variant, method=method, backend=backend,
        block_a=0, block_b=0, elapsed_s=elapsed,
        degraded=degraded, stage_reached=stage_final, mode=mode,
    )
    return SearchResult(
        ids=top.astype(np.int32), values=out_values, stats=stats, meta=meta,
        lower=out_lower, upper=out_upper,
        degraded=degraded, stage_reached=stage_final,
        certified_recall_at_k=recall,
    )


search.__doc__ = _search_impl.__doc__
