#!/usr/bin/env python3
"""Chip smoke test: the served retrieval path and the pairwise fused kernel
on a TPU, at deployment size, checked against float64 references.

    python chip_smoke.py [--seed 0]           # one chip
    python chip_smoke.py --chips 4 [--seed 0] # shards=4 vs shards=None only

Phases (one chip):

  device     — JAX's first device must be a TPU; there is no CPU fallback.
  precision  — error of an f32 d² GEMM at DEFAULT and HIGHEST precision,
               in XLA and inside a Pallas kernel, against float64; HIGHEST
               must stay inside the fp budget the certificates assume.
  retrieval  — a ColBERTv2-shaped late-interaction corpus (arXiv:2112.01488:
               unit-norm D=128 token vectors, passages of 32–180 vectors,
               queries of 32) in a ``SetStore`` behind ``ProHDService`` +
               ``QueryEngine``: exact top-10 queries one at a time and
               concurrently (one ``search_batch`` flush), one anytime query,
               and one direct ``search(on_fault="raise")``.  Every result is
               compared with a float64 NumPy brute force over the corpus.
  pairwise   — ``set_distance`` exact and ProHD on the paper's image proxy
               at 50k × 50k, D = 64, against a blocked float64 reference.

With ``--chips 4`` the script runs only ``search`` and ``search_batch`` with
``shards=4`` and without, on the same store, and checks they agree.

Each phase prints one JSON line of facts.  The last line of stdout is
``{"ok": true, "device": {...}}``; any failed check exits non-zero without
it.  All data is generated from ``--seed``.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Deployment sizes (see the module docstring and PERF.md "Cells").
N_SETS = 50_000
N_QUERIES = 10          # 1 direct + 2 one-at-a-time + 6 concurrent + 1 anytime
K = 10
ANYTIME_EPSILON = 0.01  # absolute distance; top-10 values are ~0.6 here
PAIR_N = 50_000
PAIR_D = 64
PROBE_ROWS = 512

_EPS32 = float(np.finfo(np.float32).eps)


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}, default=str), flush=True)


def peak_bytes(devices) -> dict:
    """``peak_bytes_in_use`` per device id, where the backend reports it."""
    return {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices}


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# float64 references (host NumPy, blocked)
# ---------------------------------------------------------------------------


def hausdorff_f64(a, b, block: int = 1024) -> float:
    """H(A, B) in float64, scanning A in row blocks."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    bn = np.einsum("ij,ij->i", b, b)
    row_max = 0.0
    col_min = np.full(b.shape[0], np.inf)
    for lo in range(0, a.shape[0], block):
        blk = a[lo:lo + block]
        d2 = (-2.0 * blk) @ b.T
        d2 += np.einsum("ij,ij->i", blk, blk)[:, None]
        d2 += bn[None, :]
        row_max = max(row_max, float(d2.min(axis=1).max()))
        np.minimum(col_min, d2.min(axis=0), out=col_min)
    return float(np.sqrt(max(row_max, float(col_min.max()), 0.0)))


def corpus_hd_f64(sets, queries, block_points: int = 1 << 17) -> np.ndarray:
    """(n_queries, n_sets) float64 Hausdorff distances, every pair."""
    lens = np.asarray([s.shape[0] for s in sets])
    starts = np.concatenate([[0], np.cumsum(lens)])
    q = np.concatenate(queries).astype(np.float64)
    q_offs = np.concatenate([[0], np.cumsum([x.shape[0] for x in queries])[:-1]])
    qn = np.einsum("ij,ij->i", q, q)
    out = np.empty((len(queries), len(sets)))
    i0 = 0
    while i0 < len(sets):
        i1 = max(i0 + 1, int(np.searchsorted(starts, starts[i0] + block_points, "right")) - 1)
        i1 = min(i1, len(sets))
        p = np.concatenate(sets[i0:i1]).astype(np.float64)
        offs = starts[i0:i1] - starts[i0]
        d2 = (-2.0 * q) @ p.T
        d2 += qn[:, None]
        d2 += np.einsum("ij,ij->i", p, p)[None, :]
        q_to_s = np.maximum.reduceat(np.minimum.reduceat(d2, offs, axis=1), q_offs, axis=0)
        s_to_q = np.maximum.reduceat(np.minimum.reduceat(d2, q_offs, axis=0), offs, axis=1)
        out[:, i0:i1] = np.sqrt(np.maximum(np.maximum(q_to_s, s_to_q), 0.0))
        i0 = i1
    return out


def pair_scales(sets, queries) -> np.ndarray:
    """(n_queries, n_sets) max‖q‖ + max‖s‖: the ``scale`` the repo's
    conformance suite feeds ``fp_value_margin`` for a pair."""
    s_norm = np.asarray([np.linalg.norm(s.astype(np.float64), axis=1).max() for s in sets])
    q_norm = np.asarray([np.linalg.norm(x.astype(np.float64), axis=1).max() for x in queries])
    return q_norm[:, None] + s_norm[None, :]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _probe_kernel(a_ref, b_ref, o_ref, *, precision):
    import jax

    o_ref[...] = jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=o_ref.dtype,
    )


def precision_phase(a, b) -> dict:
    """Max error of the d² GEMM term ``2·a·bᵀ`` against float64, per
    precision and per compiler, beside the d² error the certificates
    budget for: ``(D+2)·eps32·scale²``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels.hausdorff.ops import _default_interpret

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    truth = a.astype(np.float64) @ b.astype(np.float64).T
    scale = float(np.linalg.norm(a, axis=1).max() + np.linalg.norm(b, axis=1).max())
    budget = (a.shape[1] + 2) * _EPS32 * scale**2
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    facts = {"d2_budget": budget}
    for name, prec in (("default", jax.lax.Precision.DEFAULT),
                       ("highest", jax.lax.Precision.HIGHEST)):
        xla = jax.jit(lambda x, y, p=prec: jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())), precision=p,
            preferred_element_type=jnp.float32))(aj, bj)
        kern = pl.pallas_call(
            functools.partial(_probe_kernel, precision=prec),
            out_shape=jax.ShapeDtypeStruct(truth.shape, jnp.float32),
            interpret=_default_interpret(),
        )(aj, bj)
        for where, got in (("xla", xla), ("pallas", kern)):
            facts[f"{where}_{name}_d2_err"] = float(
                2.0 * np.abs(np.asarray(got, np.float64) - truth).max()
            )
    for where in ("xla", "pallas"):
        err = facts[f"{where}_highest_d2_err"]
        check(err <= budget, f"precision: {where} HIGHEST d² error {err} exceeds "
                             f"the certified budget {budget}")
    return facts


def build_store(seed: int, n_sets: int, n_queries: int):
    """The late-interaction corpus in a SetStore, slabs on the device."""
    import jax

    from repro.data.pointclouds import late_interaction_sets
    from repro.index import SetStore

    sets, queries = late_interaction_sets(seed, n_sets, n_queries)
    store = SetStore(dim=sets[0].shape[1])
    store.add_many(sets)
    buckets = store.packed_buckets()
    jax.block_until_ready([b.points for b in buckets.values()])
    device_bytes = sum(
        b.points.nbytes + b.valid.nbytes + b.sqnorms.nbytes for b in buckets.values()
    )
    facts = {
        "sets": len(sets), "points": int(sum(s.shape[0] for s in sets)),
        "dim": store.dim, "slab_bytes_on_device": int(device_bytes),
        "buckets": {int(c): int(b.set_ids.size) for c, b in buckets.items()},
    }
    return store, sets, queries, facts


def check_healthy(res, label: str) -> None:
    check(not res.degraded, f"{label}: degraded at {res.stage_reached}")
    check("fault" not in res.stats, f"{label}: fault {res.stats.get('fault')}")
    check(not res.stats.get("backend_fallbacks"),
          f"{label}: backend fallbacks {res.stats.get('backend_fallbacks')}")


def check_exact(res, ref, scales, k: int, dim: int, label: str) -> None:
    """ids equal float64 brute force's (value, id) ranking; values within
    ``fp_value_margin`` of the float64 distances."""
    from repro.index import fp_value_margin

    check_healthy(res, label)
    want = np.lexsort((np.arange(ref.size), ref))[:k]
    check(np.array_equal(np.asarray(res.ids), want),
          f"{label}: ids {list(res.ids)} != float64 brute force {list(want)}")
    got = np.asarray(res.values, np.float64)
    margin = fp_value_margin(dim, scales[want], got)
    err = np.abs(got - ref[want])
    check(bool(np.all(err <= margin)),
          f"{label}: |value - float64| {err.max()} exceeds fp_value_margin")


def check_anytime(res, ref, scales, k: int, dim: int, epsilon: float,
                  label: str) -> dict:
    """The ε-approximate top-k contract against float64: every returned
    interval holds its float64 distance (within ``fp_value_margin``, as an
    exact value would), no excluded set beats ``τ − ε``, and the certified
    recall never overstates the true recall."""
    from repro.index import fp_value_margin

    check_healthy(res, label)
    check(res.stats.get("converged", True), f"{label}: anytime did not converge")
    ids = np.asarray(res.ids)
    m = fp_value_margin(dim, scales[ids], ref[ids])
    check(bool(np.all((res.lower - m <= ref[ids]) & (ref[ids] <= res.upper + m))),
          f"{label}: a returned interval misses its float64 distance")
    tau = float(np.max(res.upper))
    out = np.ones(ref.size, bool)
    out[ids] = False
    check(bool(np.all(ref[out] + fp_value_margin(dim, scales[out], ref[out])
                      > tau - epsilon)),
          f"{label}: an excluded set beats the top-k by more than epsilon")
    exact_top = np.lexsort((np.arange(ref.size), ref))[:k]
    recall = len(set(ids.tolist()) & set(exact_top.tolist())) / k
    check(res.certified_recall_at_k <= recall + 1e-12,
          f"{label}: certified recall {res.certified_recall_at_k} > true {recall}")
    return {"certified_recall_at_k": res.certified_recall_at_k,
            "true_recall_at_k": recall}


def _stage_facts(res) -> dict:
    keys = ("stage0_pruned", "stage1_pruned", "stage2_batched_candidates",
            "exact_refines", "masked_backend", "refine_backend", "batch_queries")
    return {key: res.stats[key] for key in keys if key in res.stats}


def retrieval_phase(store, queries, ref, scales, *, k: int,
                    epsilon: float) -> dict:
    """Drive the served path; check every result against float64."""
    from repro.index import search
    from repro.serve.engine import EngineConfig, QueryEngine
    from repro.serve.server import ProHDService

    dim = store.dim
    t0 = time.perf_counter()
    direct = search(queries[0], store, k, on_fault="raise")
    check_exact(direct, ref[0], scales[0], k, dim, "search(q0)")
    t_direct = time.perf_counter() - t0

    engine = QueryEngine(ProHDService(store=store),
                         EngineConfig(max_batch=16, max_wait_s=0.05))
    n_many = len(queries) - 4

    async def drive():
        try:
            one = [await engine.search(q, k) for q in queries[1:3]]
            many = await asyncio.gather(
                *(engine.search(q, k) for q in queries[3:3 + n_many]))
            anytime = await engine.search(queries[-1], k, mode="anytime",
                                          epsilon=epsilon)
        finally:
            await engine.close()
        return one, list(many), anytime

    t0 = time.perf_counter()
    one, many, anytime = asyncio.run(drive())
    t_engine = time.perf_counter() - t0
    for i, res in enumerate(one + many, start=1):
        check_exact(res, ref[i], scales[i], k, dim, f"engine.search(q{i})")
    check(all(r.stats.get("batch_queries") == n_many for r in many),
          f"concurrent queries did not share one flush: "
          f"{[r.stats.get('batch_queries') for r in many]}")
    any_facts = check_anytime(anytime, ref[-1], scales[-1], k, dim, epsilon,
                              "engine.search(anytime)")
    return {
        "search_masked_backend": direct.stats["masked_backend"],
        "engine_masked_backends": sorted(
            {r.stats["masked_backend"] for r in one + many + [anytime]}),
        "refine_backends": sorted(
            {r.stats["refine_backend"] for r in [direct] + one + many}),
        "direct": _stage_facts(direct),
        "concurrent": _stage_facts(many[0]),
        "engine_flushes": engine.stats["flushes"],
        "anytime": any_facts,
        "direct_search_s": t_direct,
        "engine_s": t_engine,
    }


def stage2a_programs(store, n_q: int, *, single_backend: str,
                     multi_backend: str) -> dict:
    """Compiled text of both stage-2a programs at the largest bucket's
    frontier shape, for the backends the searches resolved."""
    import jax
    import jax.numpy as jnp

    from repro.hd import resolver
    from repro.index import cascade, multiquery
    from repro.index.store import bucket_capacity

    bucket = max(store.packed_buckets().values(), key=lambda b: b.set_ids.size)
    cap, dim = bucket.capacity, store.dim
    batch = bucket_capacity(bucket.set_ids.size, 1)
    nq = bucket_capacity(n_q)
    kind = resolver.default_device_kind()
    f32, b8 = jnp.float32, jnp.bool_
    sds = jax.ShapeDtypeStruct

    def blocks(be):
        return resolver.resolve_block_sizes(
            nq, cap, dim, device_kind=kind,
            backend="fused_pallas" if be.endswith("_pallas") else "tiled")

    ba, bb = blocks(single_backend)
    single = cascade._stage2_batch.lower(
        sds((n_q, dim), f32), sds((batch, cap, dim), f32), sds((batch, cap), b8),
        sds((batch,), f32), sds((batch,), f32),
        directed=False, backend=single_backend, block_a=ba, block_b=bb,
    ).compile().as_text()
    ba, bb = blocks(multi_backend)
    q_pad = 8
    multi = multiquery._stage2a_multiquery.lower(
        sds((q_pad, nq, dim), f32), sds((q_pad, nq), b8),
        sds((batch, cap, dim), f32), sds((batch, cap), b8),
        sds((q_pad, batch), f32), sds((q_pad, batch), f32),
        directed=False, backend=multi_backend, block_a=ba, block_b=bb,
    ).compile().as_text()
    return {"search": single, "search_batch": multi}


def pairwise_phase(seed: int, n: int, d: int) -> dict:
    """Exact and ProHD ``set_distance`` on the image proxy vs float64."""
    import jax

    from repro.data.pointclouds import gaussian_mixture_pca
    from repro.hd import set_distance
    from repro.index import fp_value_margin

    a, b = gaussian_mixture_pca(jax.random.PRNGKey(seed), n, n, d)
    t0 = time.perf_counter()
    exact = set_distance(a, b, method="exact")
    value = float(exact.value)
    t_exact = time.perf_counter() - t0
    t0 = time.perf_counter()
    prohd = set_distance(a, b, method="prohd")
    lower, upper = float(prohd.lower), float(prohd.upper)
    t_prohd = time.perf_counter() - t0
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    ref = hausdorff_f64(a64, b64)
    scale = float(np.linalg.norm(a64, axis=1).max() + np.linalg.norm(b64, axis=1).max())
    margin = float(fp_value_margin(d, scale, value))
    check(abs(value - ref) <= margin,
          f"pairwise exact {value} vs float64 {ref}: off by more than {margin}")
    check(lower <= ref <= upper,
          f"pairwise ProHD interval [{lower}, {upper}] misses float64 {ref}")
    return {
        "n": n, "dim": d, "exact_backend": exact.meta.backend,
        "prohd_backend": prohd.meta.backend, "exact": value, "float64": ref,
        "margin": margin, "prohd": float(prohd.value),
        "prohd_interval": [lower, upper],
        "exact_s": t_exact, "prohd_s": t_prohd,
    }


def sharded_phase(store, queries, ref, scales, *, k: int, shards: int) -> dict:
    """search and search_batch with shards=p and without: identical top-k,
    both equal to float64 brute force."""
    import jax

    from repro.index import search, search_batch

    dim = store.dim
    plain = search(queries[0], store, k, on_fault="raise")
    t0 = time.perf_counter()
    split = search(queries[0], store, k, shards=shards, on_fault="raise")
    t_search = time.perf_counter() - t0
    plain_b = search_batch(queries, store, k, on_fault="raise")
    t0 = time.perf_counter()
    split_b = search_batch(queries, store, k, shards=shards, on_fault="raise")
    t_batch = time.perf_counter() - t0
    pairs = [(plain, split, 0)] + [(p, s, i) for i, (p, s) in enumerate(zip(plain_b, split_b))]
    for p, s, i in pairs:
        check_exact(p, ref[i], scales[i], k, dim, f"shards=None q{i}")
        check_healthy(s, f"shards={shards} q{i}")
        check(np.array_equal(p.ids, s.ids) and np.array_equal(p.values, s.values),
              f"q{i}: shards={shards} top-k differs from shards=None")
    buckets = store.packed_buckets()
    slab_devices = sorted({d.id for b in buckets.values() for d in b.points.devices()})
    return {
        "shards": shards, "queries": len(queries),
        "slab_devices": slab_devices,
        "summary_devices": sorted(d.id for d in store.summaries().centroid.devices()),
        "peak_bytes_in_use": peak_bytes(jax.devices()[:shards]),
        "sharded_search_stage1_pruned": split.stats.get("stage1_pruned"),
        "sharded_search_s": t_search, "sharded_search_batch_s": t_batch,
    }


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run(seed: int, chips: int) -> dict:
    device = device_info()
    check(device["platform"] == "tpu",
          f"JAX found no TPU (first device is {device['platform']!r})")
    check(device["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {device['count']}")

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.hausdorff.ops import _default_interpret

    check(not _default_interpret(), "Pallas kernels would run in interpret mode")
    cache_dir = enable_compile_cache()
    emit("device", **device, compile_cache=cache_dir)

    t0 = time.perf_counter()
    store, sets, queries, facts = build_store(seed, N_SETS, N_QUERIES)
    check(facts["slab_bytes_on_device"] >= 1 << 30,
          f"corpus slabs hold {facts['slab_bytes_on_device']} B, under 1 GiB")
    emit("corpus", **facts, build_s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    ref = corpus_hd_f64(sets, queries)
    scales = pair_scales(sets, queries)
    emit("reference", queries=len(queries), seconds=time.perf_counter() - t0)

    if chips > 1:
        phases = {"sharded": lambda: sharded_phase(
            store, queries, ref, scales, k=K, shards=chips)}
    else:
        tokens = np.concatenate(sets[: 2 * PROBE_ROWS // 32])  # >= 32 tokens a set
        phases = {
            "precision": lambda: precision_phase(
                tokens[:PROBE_ROWS], tokens[PROBE_ROWS:2 * PROBE_ROWS]),
            "retrieval": lambda: served_retrieval(store, queries, ref, scales),
            "pairwise": lambda: fused_pairwise(seed),
        }
    failed = []
    for name, phase in phases.items():
        try:
            emit(name, **phase())
        except Exception as e:  # noqa: BLE001 - report it, then run the rest
            traceback.print_exc()
            failed.append(f"{name}: {e}")
    check(not failed, "; ".join(failed))
    return device


def served_retrieval(store, queries, ref, scales) -> dict:
    """The retrieval phase plus the checks only a TPU can pass."""
    import jax

    facts = retrieval_phase(store, queries, ref, scales, k=K, epsilon=ANYTIME_EPSILON)
    check(facts["search_masked_backend"] == "batched_pallas",
          f"search resolved {facts['search_masked_backend']}, not batched_pallas")
    check(facts["engine_masked_backends"] == ["multiquery_pallas"],
          f"engine resolved {facts['engine_masked_backends']}, not multiquery_pallas")
    progs = stage2a_programs(store, queries[0].shape[0],
                             single_backend=facts["search_masked_backend"],
                             multi_backend=facts["engine_masked_backends"][0])
    for path, text in progs.items():
        check("tpu_custom_call" in text, f"stage-2a program of {path} has no tpu_custom_call")
    return {**facts, "stage2a_tpu_custom_call": True,
            "peak_bytes_in_use": peak_bytes(jax.devices()[:1])}


def fused_pairwise(seed: int) -> dict:
    """The pairwise phase plus the check that exact ran the Pallas kernel."""
    facts = pairwise_phase(seed, PAIR_N, PAIR_D)
    check(facts["exact_backend"] == "fused_pallas",
          f"pairwise exact resolved {facts['exact_backend']}, not fused_pallas")
    return facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        device = run(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
