#!/usr/bin/env python3
"""The controls: each cell's plain reference put in the program's place,
one step below what the configuration guarantees, compared by the same
numbers and limits as a run.  Each must come out not correct.

    python bench/control.py --workload <name> --seed <n> [--seed <n> ...]

- retrieval cells: the brute force on the device with its operands in
  bfloat16, the precision below the float32 at HIGHEST that the certified
  margins assume, over as many queries as a run checks; the same brute
  force in float32 is printed beside it and must come out correct;
- pairwise cells: ProHD as the paper typesets Alg. 3, ``inner="subset"``,
  which the configuration's never-overestimates guarantee rules out, and
  ProHD with its products on bfloat16 operands; beside them the program
  itself, which must come out correct, and the program with a fault
  planted: ``centroid_only``, its selection cut to the centroid direction's
  extremes, which must not.

One JSON line per seed and control.  Benchmark runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from run import cell_spec, load_json  # noqa: E402


def retrieval_controls(config: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from gen import late_interaction as gen
    from reference import retrieval as ref

    shape = {k: config[k] for k in gen.SHAPE}
    flat, lens, centers, topics = gen.corpus(seed, config["n_sets"], shape)
    queries = gen.queries(seed, traffic["check_sample"], centers, topics, shape)
    hd = ref.corpus_hd_f64(flat, lens, queries)
    scales = ref.pair_scales(flat, lens, queries)
    flat_dev = jax.device_put(flat)
    set_of_row = jax.device_put(np.repeat(np.arange(lens.size, dtype=np.int32), lens))
    k, mode = traffic["k"], traffic["mode"]
    out = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        answers = []
        for q in queries:
            ids, vals = ref.brute_topk(q, flat_dev, set_of_row, lens.size, k, dtype)
            answers.append({"ids": ids, "values": vals, "lower": vals, "upper": vals,
                            "healthy": True, "converged": True, "certified_recall": 1.0})
        if mode == "anytime":
            numbers = ref.anytime_numbers(answers, hd, scales, k, shape["dim"],
                                          traffic["epsilon"])
        else:
            numbers = ref.exact_numbers(answers, hd, scales, k, shape["dim"])
        out[name] = numbers
    return out


def pairwise_controls(config: dict, traffic: dict, seed: int) -> dict:
    import jax.numpy as jnp

    from drivers.pairwise import nn_gap, sel_gap
    from gen import random_clouds as gen
    from reference import pairwise as ref
    from repro.core.prohd import ProHDConfig
    from repro.hd import HDConfig, set_distance

    d, hd = config["dim"], config["hd_config"]
    alpha = hd["alpha"]
    a, b = gen.pool(gen.key(seed), pairs=config["pool_pairs"], n=config["n"], d=d)
    picks = sorted(np.random.default_rng([seed % (1 << 64), 4]).choice(
        config["pool_pairs"], min(traffic["check_pairs"], config["pool_pairs"]),
        replace=False).tolist())

    def program(cfg):
        def call(x, y):
            r = set_distance(x, y, method=config["method"], config=cfg)
            return {"value": float(r.value), "lower": float(r.lower),
                    "upper": float(r.upper), "n_sel_a": int(r.stats["n_sel_a"]),
                    "n_sel_b": int(r.stats["n_sel_b"])}
        return call

    variants = {
        "subset": lambda x, y: ref.prohd_plain(x, y, alpha=alpha, inner="subset"),
        "bfloat16": lambda x, y: ref.prohd_plain(x, y, alpha=alpha, dtype=jnp.bfloat16),
        "program": program(HDConfig(**hd)),
        "centroid_only": program(HDConfig(prohd=ProHDConfig(**hd, num_pca_directions=0))),
    }
    out = {name: {"nn_gap": 0.0, "interval_gap": -np.inf, "sel_gap": 0.0}
           for name in variants}
    for i in picks:
        f = ref.pair_facts(a[i], b[i], alpha=alpha)
        margin = float(ref.fp_value_margin(d, f["scale"], f["exact"]))
        for name, fn in variants.items():
            c = fn(a[i], b[i])
            o = out[name]
            o["nn_gap"] = max(o["nn_gap"], nn_gap(c["value"], f["candidates"]))
            o["interval_gap"] = max(o["interval_gap"], (c["lower"] - f["exact"]) / margin,
                                    (f["exact"] - c["upper"]) / margin)
            o["sel_gap"] = max(o["sel_gap"],
                               sel_gap((c["n_sel_a"], c["n_sel_b"]), f["n_sel"]))
    return out


def controls(workload: str, seed: int, bench: dict | None = None) -> dict:
    """``{control: {"numbers", "limits", "correct"}}`` for one seed of one
    cell."""
    spec = cell_spec(bench or load_json(BENCH.parent / "BENCHMARK.json"), workload)
    config, traffic = spec["config"], spec["traffic"]
    if config["driver"] == "retrieval":
        limits = config["checks"][traffic["mode"]]
        got = retrieval_controls(config, traffic, seed)
    else:
        limits = config["checks"]
        got = pairwise_controls(config, traffic, seed)
    return {name: {"numbers": {k: float(v[k]) for k in limits},
                   "limits": limits,
                   "correct": all(v[k] <= limits[k] for k in limits)}
            for name, v in got.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    for seed in args.seed:
        for name, c in controls(args.workload, seed).items():
            print(json.dumps({"workload": args.workload, "seed": seed, "control": name, **c}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
