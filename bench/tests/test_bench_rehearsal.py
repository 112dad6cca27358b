"""Rehearsal of the benchmark on the CPU, at tiny sizes.

Each driver runs end to end through ``bench/run.py`` in this process, with
the Pallas kernels' pure-JAX mirrors, the look for a chip steered to accept
the CPU, and configurations, traffic and BENCHMARK.json entries written
into a temporary copy of ``bench/``.  It checks the shape of the last line,
that the float64 comparison passes on the program and fails on the
controls and on answers altered where they are produced, and that a new
configuration, traffic mix and per-layer metric need only new files.
No timing here means anything; only the chip gives those.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]

TINY_LI = {
    "driver": "retrieval", "n_sets": 40, "dim": 128, "min_len": 32, "max_len": 33,
    "mean_len": 32.0, "len_sigma": 0.4, "query_len": 32, "sets_per_topic": 10,
    "noise": 0.5, "engine": {"max_batch": 2, "max_wait_s": 0.002},
    "backends": {"masked": "multiquery_mirror"},
    "checks": json.loads((BENCH / "configs" / "colbertv2-li50k.json").read_text())["checks"],
}
TINY_PAIR = {
    "driver": "pairwise", "n": 512, "dim": 128, "pool_pairs": 2, "method": "prohd",
    "hd_config": {"alpha": 0.05, "inner": "full"}, "backends": {"prohd": "tiled"},
    "checks": json.loads((BENCH / "configs" / "prohd-synth-d256.json").read_text())["checks"],
}
TRAFFIC = {
    "open-exact": {"loop": "open", "rate_qps": 8.0, "mode": "exact", "k": 3,
                   "check_sample": 2, "drain_s": 30},
    "closed-anytime": {"loop": "closed", "clients": 2, "mode": "anytime", "k": 3,
                       "epsilon": 0.01, "pool": 16, "check_sample": 2, "drain_s": 30},
    "pair-closed": {"loop": "closed", "clients": 1, "check_pairs": 1},
}
CELLS = {
    "li-exact": ("li-tiny", "open-exact"),
    "li-anytime": ("li-tiny", "closed-anytime"),
    "pair": ("pair-tiny", "pair-closed"),
}
SECONDS = 0.3


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


LI_CELLS = ["li-exact", "li-anytime"]


def _metric(name, unit, better, source, cells, **kw):
    return dict(name=name, unit=unit, better=better, source=source, workloads=cells, **kw)


def _bench_json(root: Path, extra_cells=(), extra_per_layer=(), latency_cells=()) -> None:
    """The rehearsal's own BENCHMARK.json: every metric a driver and a
    reader under ``bench/`` provide, over the tiny cells."""
    li = LI_CELLS + list(latency_cells)
    e2e = [_metric("latency_p95_ms", "ms", "lower", "host_clock", li, bound=0.25),
           _metric("qps", "queries/s", "higher", "host_clock", ["li-anytime"], bound=0.25),
           _metric("pair_ms", "ms", "lower", "host_clock", ["pair"], bound=0.25),
           {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
            "source": "host_clock"}]
    layers = [
        ("engine_wait_ms", "ms", "lower", "program_span", "engine", LI_CELLS),
        ("flush_queries", "queries", "higher", "program_span", "engine", LI_CELLS),
        ("stage2a_ms", "ms", "lower", "program_span", "cascade", LI_CELLS),
        ("stage2b_ms", "ms", "lower", "program_span", "raw refines", LI_CELLS),
        ("refines_per_query", "refines", "lower", "program_counter", "raw refines", LI_CELLS),
        ("mq_kernel_ms", "ms", "lower", "device_trace", "bucket kernel", LI_CELLS),
        ("mq_roofline_pct", "%", "higher", "device_trace", "bucket kernel", LI_CELLS),
        ("device_idle_pct.retrieval", "%", "lower", "device_trace", "device", LI_CELLS),
        ("fused_kernel_ms", "ms", "lower", "device_trace", "fused kernel", ["pair"]),
        ("fused_roofline_pct", "%", "higher", "device_trace", "fused kernel", ["pair"]),
        ("device_idle_pct.pair", "%", "lower", "device_trace", "device", ["pair"]),
    ]
    per_layer = [_metric(n, u, b, src, cells, layer=layer,
                         moves="pair_ms" if cells == ["pair"] else "latency_p95_ms")
                 for n, u, b, src, layer, cells in layers]
    cells = [{"name": n, "config": c, "traffic": t, "chips": 1, "why": "rehearsal"}
             for n, (c, t) in CELLS.items()]
    _write(root / "BENCHMARK.json", {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 1,
        "configs": [], "workloads": cells + list(extra_cells), "end_to_end": e2e,
        "per_layer": per_layer + list(extra_per_layer)})


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    _write(root / "bench" / "configs" / "li-tiny.json", TINY_LI)
    _write(root / "bench" / "configs" / "pair-tiny.json", TINY_PAIR)
    for name, t in TRAFFIC.items():
        _write(root / "bench" / "traffic" / f"{name}.json", t)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}  # rehearsal only
    _write(root / "bench" / "peaks.json", peaks)
    _bench_json(root)
    return root


def _harness(root: Path, monkeypatch, chip: bool = False):
    """``bench/run.py`` of the copy, its look for a chip steered to the CPU."""
    spec = importlib.util.spec_from_file_location("bench_run_rehearsal", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "use_compile_cache", lambda: None)
    if not chip:
        monkeypatch.setattr(mod, "require_chip", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": chips})
        monkeypatch.setattr(mod, "require_native_kernels", lambda: None)
    return mod


def _run(mod, capsys, cell: str, trace: int = 0, seed: int = 2**31 + 11):
    rc = mod.main(["--workload", cell, "--seed", str(seed), "--seconds", str(SECONDS),
                   "--trace", str(trace)])
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines and rc == 0 else None), err


def _cell_metrics(root: Path, cell: str, kind: str) -> set[str]:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return {m["name"] for m in bench[kind] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_run_prints_the_contract_line(root, monkeypatch, capsys, cell):
    rc, res, err = _run(_harness(root, monkeypatch), capsys, cell)
    assert rc == 0, err
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == _cell_metrics(root, cell, "end_to_end")
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and "memory_peak_bytes" in res["device"]
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [line.split()[1] for line in tail] == list(res["checks"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_run_reads_the_per_layer_metrics(root, monkeypatch, capsys, cell):
    rc, res, err = _run(_harness(root, monkeypatch), capsys, cell, trace=1)
    assert rc == 0, err
    assert res["correct"] is True, res["checks"]
    per_layer = _cell_metrics(root, cell, "per_layer")
    assert set(res["metrics"]) <= per_layer
    assert {m for m in per_layer if "idle" in m} <= set(res["metrics"])
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(res["breakdown"]["device_ops"]) <= 10


def test_no_chip_no_result(root, monkeypatch, capsys):
    rc, res, err = _run(_harness(root, monkeypatch, chip=True), capsys, "li-exact")
    assert rc != 0 and res is None
    assert "no TPU" in err


def _alter_search_batch(monkeypatch):
    import repro.index.multiquery as mq

    real = mq.search_batch

    def altered(*args, **kwargs):
        out = []
        for r in real(*args, **kwargs):
            ids = r.ids.copy()
            ids[0] = (int(ids.max()) + 1) % 100
            out.append(dataclasses.replace(r, ids=ids, values=r.values + 0.05,
                                           lower=r.lower + 0.05, upper=r.upper + 0.05))
        return out

    monkeypatch.setattr(mq, "search_batch", altered)


def _alter_set_distance(monkeypatch):
    import repro.hd as hd

    real = hd.set_distance

    def altered(*args, **kwargs):
        r = real(*args, **kwargs)
        return dataclasses.replace(r, value=r.value * 1.01)

    monkeypatch.setattr(hd, "set_distance", altered)


def _centroid_only_selection(monkeypatch):
    import repro.hd as hd
    from repro.core.prohd import ProHDConfig

    real = hd.set_distance

    def altered(*args, config, **kwargs):
        cut = ProHDConfig(alpha=config.alpha, inner=config.inner, num_pca_directions=0)
        return real(*args, config=dataclasses.replace(config, prohd=cut), **kwargs)

    monkeypatch.setattr(hd, "set_distance", altered)


@pytest.mark.parametrize("cell,alter", [("li-exact", _alter_search_batch),
                                        ("li-anytime", _alter_search_batch),
                                        ("pair", _alter_set_distance),
                                        ("pair", _centroid_only_selection)])
def test_an_altered_answer_is_not_correct(root, monkeypatch, capsys, cell, alter):
    mod = _harness(root, monkeypatch)
    alter(monkeypatch)
    rc, res, err = _run(mod, capsys, cell)
    assert rc == 0, err
    assert res["correct"] is False
    assert "FAIL" in err


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_control_is_not_correct(root, monkeypatch, cell):
    mod = _harness(root, monkeypatch)  # puts the copy's bench/ on sys.path
    import sys

    sys.path.insert(0, str(root / "bench"))
    spec = importlib.util.spec_from_file_location("bench_control_rehearsal",
                                                  root / "bench" / "control.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    got = control.controls(cell, 5, json.loads((root / "BENCHMARK.json").read_text()))
    del mod
    if cell == "pair":
        assert got["subset"]["correct"] is False, got
        assert got["program"]["correct"] is True, got
        assert got["centroid_only"]["correct"] is False, got
        assert got["centroid_only"]["numbers"]["sel_gap"] > got["centroid_only"]["limits"]["sel_gap"]
    else:
        assert got["float32"]["correct"] is True, got
        assert got["bfloat16"]["correct"] is False, got


def test_new_files_are_found_by_name(root, monkeypatch, capsys):
    cfg = dict(TINY_LI, n_sets=60)
    _write(root / "bench" / "configs" / "li-newer.json", cfg)
    _write(root / "bench" / "traffic" / "closed-exact.json",
           {"loop": "closed", "clients": 2, "mode": "exact", "k": 2, "pool": 8,
            "check_sample": 1, "drain_s": 30})
    (root / "bench" / "layers" / "results_seen.py").write_text(
        "def read(observed):\n    return len(observed['results']) or None\n")
    _bench_json(
        root,
        extra_cells=[{"name": "li-newer", "config": "li-newer", "traffic": "closed-exact",
                      "chips": 1, "why": "rehearsal"}],
        extra_per_layer=[{"name": "results_seen", "unit": "results", "better": "higher",
                          "source": "program_counter", "layer": "engine",
                          "moves": "latency_p95_ms", "workloads": ["li-newer"]}],
        latency_cells=["li-newer"])
    mod = _harness(root, monkeypatch)
    rc, res, err = _run(mod, capsys, "li-newer")
    assert rc == 0, err
    assert res["correct"] is True and set(res["metrics"]) == {"latency_p95_ms", "setup_s"}
    rc, res, err = _run(mod, capsys, "li-newer", trace=1)
    assert rc == 0, err
    assert res["metrics"]["results_seen"]["value"] >= 1
