"""Driver for the served late-interaction retrieval cells.

Entry: ``repro.serve.engine.QueryEngine.search`` over ``ProHDService`` and
a ``SetStore`` that holds the whole corpus on the device.  The driver
builds the corpus and the query pool from the seed, warms up the programs
the cell's traffic runs, drives the window as an open loop (requests due at
fixed times) or a closed loop (clients that each wait for their answer),
and then checks a sample of the answers, drawn from the seed, against the
float64 brute force.

Traffic keys: ``loop`` ("open" or "closed"), ``rate_qps`` (open) or
``clients`` (closed), ``mode``, ``k``, ``epsilon``, ``pool`` (closed: the
number of distinct queries cycled), ``check_sample`` and ``drain_s``, the
time an answer due in the window is waited for after it closes.
"""
from __future__ import annotations

import asyncio
import itertools
import math
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from gen import late_interaction as gen
from reference import retrieval as ref

# Threads that compile the warm-up's independent programs at once.
WARM_THREADS = 8

def pad_classes(max_batch: int) -> list[int]:
    """One flush size per query-axis pad a flush can run at: a flush of
    ``n`` distinct queries pads to the next power of two."""
    return sorted({min(1 << i, max_batch) for i in range(max_batch.bit_length() + 1)})


def open_loop_due(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times of an open loop: ``round(rate·seconds)`` gaps at the
    midpoint quantiles of the exponential distribution, in an order drawn
    from the seed, so that every seed offers the same arrivals in another
    order."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gen.rng(seed, 3).shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank; ``inf`` counts as a value."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q * v.size) - 1)])


def healthy(res) -> bool:
    return (not res.degraded and "fault" not in res.stats
            and not res.stats.get("backend_fallbacks"))


def build(h):
    """The corpus in a ``SetStore`` with its slabs on the device."""
    import jax

    from repro.index import SetStore

    shape = {k: h.config[k] for k in gen.SHAPE}
    flat, lens, centers, topics = gen.corpus(h.seed, h.config["n_sets"], shape)
    store = SetStore(dim=shape["dim"])
    store.add_many(gen.split(flat, lens))
    buckets = store.packed_buckets()
    jax.block_until_ready([b.points for b in buckets.values()])
    facts = {
        "sets": int(lens.size), "points": int(lens.sum()),
        "slab_bytes_on_device": int(sum(
            b.points.nbytes + b.valid.nbytes + b.sqnorms.nbytes
            for b in buckets.values())),
        "buckets": {int(c): int(b.set_ids.size) for c, b in buckets.items()},
    }
    return store, flat, lens, centers, topics, shape, facts


def warm_up(h, store, engine, warm, lens, knobs) -> dict:
    """Compile or load every program the cell's traffic runs: one raw
    refine per passage length, stage 0 and stage 2a at each query-axis pad
    a flush can have, then the engine's own path.  The programs are
    independent, so they compile on ``WARM_THREADS`` threads at once."""
    import jax.numpy as jnp

    from repro.hd import set_distance
    from repro.index.multiquery import search_batch

    k = knobs["k"]
    t = time.perf_counter()
    res = search_batch(list(warm[:1]), store, [k], on_fault="raise",
                       validate=False, **knobs["modes"])
    h.expect("stage 2a", res[0].stats["masked_backend"], h.config["backends"]["masked"])
    refine = res[0].stats["refine_backend"]
    h.note("warm_up", flush_queries=1, seconds=time.perf_counter() - t)

    q = jnp.asarray(warm[0])
    first_of_len = {}
    for sid, n in enumerate(lens):
        first_of_len.setdefault(int(n), sid)

    def one_refine(sid):
        return float(set_distance(q, store.get(sid), method="exact", backend=refine).value)

    def one_flush(n):
        return search_batch(list(warm[:n]), store, [k] * n, on_fault="raise",
                            validate=False, **knobs["modes"])

    t = time.perf_counter()
    with ThreadPoolExecutor(WARM_THREADS) as pool:
        list(pool.map(one_refine, first_of_len.values()))
    h.note("warm_up", refine_lengths=len(first_of_len), seconds=time.perf_counter() - t)
    t = time.perf_counter()
    with ThreadPoolExecutor(WARM_THREADS) as pool:
        flushes = list(pool.map(one_flush, pad_classes(engine.cfg.max_batch)[1:]))
    h.note("warm_up", flush_queries=pad_classes(engine.cfg.max_batch)[1:],
           seconds=time.perf_counter() - t)
    for out in flushes:
        for r in out:
            h.expect("stage 2a", r.stats["masked_backend"], h.config["backends"]["masked"])

    async def burst():
        for n in (1, engine.cfg.max_batch):
            out = await asyncio.gather(*(engine.search(x, k, **knobs["modes"])
                                         for x in warm[:n]))
            for r in out:
                h.expect("engine stage 2a", r.stats["masked_backend"],
                         h.config["backends"]["masked"])
    t = time.perf_counter()
    asyncio.run(burst())
    h.note("warm_up", engine_bursts=[1, engine.cfg.max_batch], seconds=time.perf_counter() - t)
    return {"refine_backend": refine, "refine_lengths": len(first_of_len)}


async def drive(h, engine, pool, knobs) -> tuple[list, float]:
    """The window: every request's record, and the window's start."""
    loop = asyncio.get_running_loop()
    tr = h.traffic
    recs: list[dict] = []

    async def one(qi, t_due):
        rec = {"q": qi, "due": t_due, "sent": loop.time()}
        recs.append(rec)
        try:
            rec["res"] = await engine.search(pool[qi], knobs["k"], **knobs["modes"])
        except Exception as e:  # noqa: BLE001 - a failed request is counted
            rec["error"] = repr(e)
        rec["done"] = loop.time()

    t0 = loop.time()
    t_end = t0 + h.seconds
    if tr["loop"] == "open":
        tasks = []
        for i, d in enumerate(open_loop_due(h.seed, tr["rate_qps"], h.seconds)):
            delay = t0 + d - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(one(i % len(pool), t0 + d)))
    else:
        counter = itertools.count()

        async def client():
            while loop.time() < t_end:
                await one(next(counter) % len(pool), loop.time())

        tasks = [asyncio.create_task(client()) for _ in range(tr["clients"])]
    _, late = await asyncio.wait(tasks, timeout=max(0.0, t_end - loop.time()) + tr["drain_s"])
    for t in late:
        t.cancel()
    await asyncio.gather(*late, return_exceptions=True)
    await engine.close()
    return recs, t0


def answer(rec) -> dict | None:
    """What the comparison reads of one request's answer."""
    if "res" not in rec:
        return None
    res = rec["res"]
    return {"ids": res.ids, "values": res.values, "lower": res.lower,
            "upper": res.upper, "healthy": healthy(res),
            "converged": res.stats.get("converged", True),
            "certified_recall": res.certified_recall_at_k}


def run(h) -> dict:
    from repro.serve.engine import EngineConfig, QueryEngine
    from repro.serve.server import ProHDService

    tr = h.traffic
    store, flat, lens, centers, topics, shape, facts = build(h)
    h.note("corpus", **facts)
    modes = {"mode": tr["mode"]}
    if tr["mode"] == "anytime":
        modes["epsilon"] = tr["epsilon"]
    knobs = {"k": tr["k"], "modes": modes}
    engine = QueryEngine(ProHDService(store=store), EngineConfig(**h.config["engine"]))
    warm = gen.queries(h.seed, engine.cfg.max_batch, centers, topics, shape, stream=2)
    facts.update(warm_up(h, store, engine, warm, lens, knobs))
    n_pool = (len(open_loop_due(h.seed, tr["rate_qps"], h.seconds))
              if tr["loop"] == "open" else tr["pool"])
    pool = gen.queries(h.seed, n_pool, centers, topics, shape)
    flushes0 = engine.stats["flushes"]

    with h.window():
        recs, t0 = asyncio.run(drive(h, engine, pool, knobs))
    t_end = t0 + h.seconds

    ok = [r for r in recs if "res" in r and healthy(r["res"])]
    lat = [r["done"] - r["due"] if r in ok else math.inf for r in recs]
    metrics = {"latency_p95_ms": 1e3 * nearest_rank(lat, 0.95),
               "qps": sum(r["done"] <= t_end for r in ok) / h.seconds}
    facts.update(
        requests=len(recs), completed_ok=len(ok),
        latency_p50_ms=1e3 * nearest_rank(lat, 0.50),
        generator_late_ms_max=1e3 * max((r["sent"] - r["due"] for r in recs), default=0.0),
        flushes=engine.stats["flushes"] - flushes0,
        stage_reached=sorted({r["res"].stage_reached for r in ok}),
    )
    observed = {
        "results": [r["res"].stats for r in ok],
        "work": {"dim": shape["dim"], "query_len": shape["query_len"],
                 "lens_cumsum": np.cumsum(np.sort(lens))},
    }

    # Checks, once the window has closed: a sample of the requests drawn
    # from the seed against the float64 brute force on the host.
    del engine, store
    t_ref = time.perf_counter()
    n_check = min(tr["check_sample"], len(recs))
    picks = np.sort(gen.rng(h.seed, 4).choice(len(recs), n_check, replace=False))
    queries = [pool[recs[i]["q"]] for i in picks]
    hd = ref.corpus_hd_f64(flat, lens, queries)
    scales = ref.pair_scales(flat, lens, queries)
    answers = [answer(recs[i]) for i in picks]
    if tr["mode"] == "anytime":
        numbers = ref.anytime_numbers(answers, hd, scales, tr["k"], shape["dim"], tr["epsilon"])
    else:
        numbers = ref.exact_numbers(answers, hd, scales, tr["k"], shape["dim"])
    facts["reference_s"] = time.perf_counter() - t_ref
    limits = h.config["checks"][tr["mode"]]
    return {"attempted": len(recs), "failed": len(recs) - len(ok), "metrics": metrics,
            "checks": {k: (numbers[k], limits[k]) for k in limits},
            "observed": observed, "facts": facts}
