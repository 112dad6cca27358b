"""References and comparisons for the late-interaction retrieval cells.

The float64 brute force and the checks are copies of those that
``chip_smoke.py`` proved on the chip, kept with the benchmark so that a
change to the program cannot move the yardstick.  They import nothing of
``repro``.  Each check returns numbers, which the harness prints beside
their limits, instead of raising.

:func:`brute_topk` is the same brute force in ``jax.numpy`` on the device;
with ``dtype=bfloat16`` it is the control, the reference computed one
precision below the float32 at HIGHEST that the certificates assume.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS32 = float(np.finfo(np.float32).eps)


def fp_value_margin(dim: int, scale, value):
    """How far two float32 computations of one distance ``value`` may lie
    apart: with ``E = (dim+2)·eps32·scale²`` bounding the d² error,
    ``2·√E`` near zero and ``2·E/(value − √E) + 1e-6`` elsewhere."""
    e = (dim + 2) * _EPS32 * np.asarray(scale, np.float64) ** 2
    sqrt_e = np.sqrt(e)
    lo = np.maximum(np.asarray(value, np.float64) - sqrt_e, 0.0)
    return np.where(lo > sqrt_e, 2.0 * e / np.maximum(lo, 1e-300), 2.0 * sqrt_e) + 1e-6


def corpus_hd_f64(flat, lens, queries, block_points: int = 1 << 17) -> np.ndarray:
    """(n_queries, n_sets) float64 Hausdorff distances, every pair, from
    the flat corpus (rows of passage ``i`` follow those of ``i − 1``)."""
    lens = np.asarray(lens)
    starts = np.concatenate([[0], np.cumsum(lens)])
    q = np.concatenate(list(queries)).astype(np.float64)
    q_offs = np.concatenate([[0], np.cumsum([x.shape[0] for x in queries])[:-1]])
    qn = np.einsum("ij,ij->i", q, q)
    n_sets = lens.size
    out = np.empty((len(queries), n_sets))
    i0 = 0
    while i0 < n_sets:
        i1 = max(i0 + 1, int(np.searchsorted(starts, starts[i0] + block_points, "right")) - 1)
        i1 = min(i1, n_sets)
        p = flat[starts[i0]:starts[i1]].astype(np.float64)
        offs = starts[i0:i1] - starts[i0]
        d2 = (-2.0 * q) @ p.T
        d2 += qn[:, None]
        d2 += np.einsum("ij,ij->i", p, p)[None, :]
        q_to_s = np.maximum.reduceat(np.minimum.reduceat(d2, offs, axis=1), q_offs, axis=0)
        s_to_q = np.maximum.reduceat(np.minimum.reduceat(d2, q_offs, axis=0), offs, axis=1)
        out[:, i0:i1] = np.sqrt(np.maximum(np.maximum(q_to_s, s_to_q), 0.0))
        i0 = i1
    return out


def pair_scales(flat, lens, queries) -> np.ndarray:
    """(n_queries, n_sets) ``max‖q‖ + max‖s‖``: the scale of each pair's
    fp margin."""
    norms = np.linalg.norm(flat.astype(np.float64), axis=1)
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    s_norm = np.maximum.reduceat(norms, starts)
    q_norm = np.asarray([np.linalg.norm(x.astype(np.float64), axis=1).max() for x in queries])
    return q_norm[:, None] + s_norm[None, :]


@functools.partial(jax.jit, static_argnames=("n_sets", "k", "dtype"))
def _brute_topk(q, flat, set_of_row, *, n_sets, k, dtype):
    q, flat = q.astype(dtype), flat.astype(dtype)
    qf, pf = q.astype(jnp.float32), flat.astype(jnp.float32)
    dot = jnp.matmul(q, flat.T, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    d2 = jnp.maximum(jnp.sum(qf * qf, 1)[:, None] + jnp.sum(pf * pf, 1)[None, :]
                     - 2.0 * dot, 0.0)                       # (n_q, rows)
    s_to_q = jax.ops.segment_max(d2.min(axis=0), set_of_row, n_sets)
    q_to_s = jax.vmap(lambda r: jax.ops.segment_min(r, set_of_row, n_sets))(d2).max(axis=0)
    hd = jnp.sqrt(jnp.maximum(s_to_q, q_to_s))
    neg, ids = jax.lax.top_k(-hd, k)
    return ids, -neg


def brute_topk(q, flat_dev, set_of_row, n_sets: int, k: int, dtype=jnp.float32):
    """``(ids, values)`` of the ``k`` sets nearest query ``q`` by brute
    force on the device, the operands cast to ``dtype``."""
    ids, vals = _brute_topk(jnp.asarray(q), flat_dev, set_of_row,
                            n_sets=n_sets, k=k, dtype=dtype)
    ids, vals = np.asarray(ids), np.asarray(vals)
    order = np.lexsort((ids, vals))
    return ids[order], vals[order]


def in_corpus(ids: np.ndarray, n_sets: int) -> bool:
    """Whether every id names a set of the corpus; an answer that names
    none has no distance to hold, and reads an infinite gap."""
    return bool(np.all((ids >= 0) & (ids < n_sets)))


def exact_numbers(answers, ref, scales, k: int, dim: int) -> dict:
    """The exact top-k contract over the sampled requests.

    ``answers`` holds, per sampled request, ``None`` where no answer came
    or a dict with ``ids``, ``values`` and ``healthy``.  Returns:
    ``missing`` (no answer), ``unhealthy`` (degraded, faulted or fell back
    to another backend), ``id_mismatch`` (ids other than float64 brute
    force's (value, id) ranking) and ``value_gap`` (largest
    ``|value − float64| / fp_value_margin``)."""
    out = {"missing": 0, "unhealthy": 0, "id_mismatch": 0, "value_gap": 0.0}
    for ans, r, s in zip(answers, ref, scales):
        if ans is None:
            out["missing"] += 1
            continue
        out["unhealthy"] += int(not ans["healthy"])
        want = np.lexsort((np.arange(r.size), r))[:k]
        ids = np.asarray(ans["ids"])
        out["id_mismatch"] += int(not np.array_equal(ids, want))
        if not in_corpus(ids, r.size):
            out["value_gap"] = np.inf
            continue
        got = np.asarray(ans["values"], np.float64)
        gap = np.abs(got - r[ids]) / fp_value_margin(dim, s[ids], got)
        out["value_gap"] = max(out["value_gap"], float(gap.max(initial=0.0)))
    return out


def anytime_numbers(answers, ref, scales, k: int, dim: int, epsilon: float) -> dict:
    """The ε-approximate top-k contract over the sampled requests.

    Besides ``missing`` and ``unhealthy``: ``unconverged`` (the ladder
    stopped short), ``interval_gap`` (largest distance of a float64 value
    outside its returned interval, over ``fp_value_margin``),
    ``excluded_beats`` (excluded sets nearer than ``τ − ε``) and
    ``recall_overstated`` (certified recall above the true recall)."""
    out = {"missing": 0, "unhealthy": 0, "unconverged": 0, "interval_gap": 0.0,
           "excluded_beats": 0, "recall_overstated": 0}
    for ans, r, s in zip(answers, ref, scales):
        if ans is None:
            out["missing"] += 1
            continue
        out["unhealthy"] += int(not ans["healthy"])
        out["unconverged"] += int(not ans["converged"])
        ids = np.asarray(ans["ids"])
        if not in_corpus(ids, r.size):
            out["interval_gap"] = np.inf
            continue
        lower = np.asarray(ans["lower"], np.float64)
        upper = np.asarray(ans["upper"], np.float64)
        m = fp_value_margin(dim, s[ids], r[ids])
        gap = np.maximum(lower - r[ids], r[ids] - upper) / m
        out["interval_gap"] = max(out["interval_gap"], float(gap.max(initial=-np.inf)))
        tau = float(upper.max())
        excluded = np.ones(r.size, bool)
        excluded[ids] = False
        beats = r[excluded] + fp_value_margin(dim, s[excluded], r[excluded]) <= tau - epsilon
        out["excluded_beats"] += int(beats.sum())
        exact_top = np.lexsort((np.arange(r.size), r))[:k]
        recall = len(set(ids.tolist()) & set(exact_top.tolist())) / k
        out["recall_overstated"] += int(ans["certified_recall"] > recall + 1e-12)
    return out
