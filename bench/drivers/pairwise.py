"""Driver for the pairwise Hausdorff cells.

Entry: ``repro.hd.set_distance`` with the configuration's ``method`` and
``HDConfig`` knobs, at the front door's defaults otherwise.  The driver
makes a pool of pairs on the device from the seed, warms up the one program
their shape needs, and drives the window as one closed-loop caller that
computes the pool's distances back to back, in turn.  Once the window has
closed, the plain reference reads a sample of the pool's pairs, drawn from
the seed, and every answer the window gave for them is compared: its value
against every point's nearest-neighbour distance (``nn_gap``), its interval
against the exact distance (``interval_gap``), and the rows it selected
against the reference's own selection (``sel_gap``).

Traffic keys: ``loop`` ("closed"), ``clients`` (1) and ``check_pairs``, the
number of pool pairs the reference reads.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from gen import random_clouds as gen
from reference import pairwise as ref


def nn_gap(value: float, candidates: np.ndarray) -> float:
    """Relative distance from ``value`` to the nearest of ``candidates``."""
    i = np.searchsorted(candidates, value)
    near = candidates[max(i - 1, 0):i + 1]
    return float(np.abs(near - value).min() / value)


def sel_gap(n_sel, ref_n_sel) -> float:
    """Largest relative gap between the rows ProHD kept from each cloud and
    the count of the reference's own selection at the same fraction."""
    return max(abs(n - r) / r for n, r in zip(n_sel, ref_n_sel))


def run(h) -> dict:
    import jax

    from repro.hd import HDConfig, set_distance

    cfg, tr = h.config, h.traffic
    n_pairs, n, d = cfg["pool_pairs"], cfg["n"], cfg["dim"]
    a, b = gen.pool(gen.key(h.seed), pairs=n_pairs, n=n, d=d)
    jax.block_until_ready((a, b))
    hd_cfg = HDConfig(**cfg["hd_config"])
    method = cfg["method"]

    def call(i):
        res = set_distance(a[i], b[i], method=method, config=hd_cfg)
        return {"pair": i, "value": float(res.value), "lower": float(res.lower),
                "upper": float(res.upper), "n_sel_a": int(res.stats["n_sel_a"]),
                "n_sel_b": int(res.stats["n_sel_b"]), "backend": res.meta.backend}

    first = call(0)
    h.expect(method, first["backend"], cfg["backends"][method])

    answers = []
    with h.window():
        span = (jax.profiler.TraceAnnotation if h.trace
                else lambda _name: contextlib.nullcontext())
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < h.seconds:
            with span("bench.set_distance"):
                answers.append(call(len(answers) % n_pairs))
        elapsed = time.perf_counter() - t0
    metrics = {"pair_ms": 1e3 * elapsed / len(answers)}
    observed = {"work": {"dim": d, "n_a": n, "n_b": n,
                         "n_sel": [(x["n_sel_a"], x["n_sel_b"]) for x in answers]}}

    # Checks: the reference reads a sample, drawn from the seed, of the
    # pool's pairs that the window computed.
    t_ref = time.perf_counter()
    done = sorted({x["pair"] for x in answers})
    picks = sorted(np.random.default_rng([h.seed % (1 << 64), 4]).choice(
        done, min(tr["check_pairs"], len(done)), replace=False).tolist())
    alpha = hd_cfg.alpha
    gap, interval, sel = 0.0, -np.inf, 0.0
    facts = {"pairs": {}}
    for i in picks:
        f = ref.pair_facts(a[i], b[i], alpha=alpha)
        mine = [x for x in answers if x["pair"] == i]
        margin = float(ref.fp_value_margin(d, f["scale"], f["exact"]))
        for x in mine:
            gap = max(gap, nn_gap(x["value"], f["candidates"]))
            sel = max(sel, sel_gap((x["n_sel_a"], x["n_sel_b"]), f["n_sel"]))
            interval = max(interval, (x["lower"] - f["exact"]) / margin,
                           (f["exact"] - x["upper"]) / margin)
        facts["pairs"][i] = {"exact": f["exact"], "core": f["core"],
                             "prohd": mine[0]["value"] if mine else None,
                             "candidates": int(f["candidates"].size),
                             "n_sel": [mine[0]["n_sel_a"], mine[0]["n_sel_b"]] if mine else None,
                             "ref_n_sel": list(f["n_sel"]),
                             "err_pct": (100 * (f["exact"] - mine[0]["value"]) / f["exact"]
                                         if mine else None)}
    facts["reference_s"] = time.perf_counter() - t_ref
    numbers = {"nn_gap": gap, "interval_gap": interval, "sel_gap": sel}
    limits = cfg["checks"]
    return {"attempted": len(answers), "failed": 0, "metrics": metrics,
            "checks": {k: (numbers[k], limits[k]) for k in limits},
            "observed": observed, "facts": facts}
