"""The per-layer metrics that read what the ProHD distance path names
itself, rehearsed on the CPU at a tiny size.

A copy of ``bench/`` gets its own ``BENCHMARK.json`` that lists, on one tiny
pair cell, ``lower_bound_ms`` and ``selection_ms`` (device ops resolved to
the ``prohd.*`` scopes by ``repro.obs.xla.op_scopes``), ``validate_ms`` (the
front door's ``hd.validate`` span) and ``validate_idle_ms`` (device idle
time inside ``hd.validate``, placed on the trace's clock by
``layers/_clock.py``).  One traced run feeds every test.  No timing here
means anything; only the chip gives those.
"""
from __future__ import annotations

import importlib.util
import io
import json
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CELL = "pair"
METRICS = ("lower_bound_ms", "selection_ms", "validate_ms", "validate_idle_ms")
SOURCES = {"lower_bound_ms": ("device_trace", "ProHD lower bound"),
           "selection_ms": ("device_trace", "ProHD selection"),
           "validate_ms": ("program_span", "front door"),
           "validate_idle_ms": ("device_trace", "front door")}
TINY_PAIR = {
    "driver": "pairwise", "n": 512, "dim": 128, "pool_pairs": 2, "method": "prohd",
    "hd_config": {"alpha": 0.05, "inner": "full"}, "backends": {"prohd": "tiled"},
    "checks": json.loads((BENCH / "configs" / "prohd-synth-d256.json").read_text())["checks"],
}


def _write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def _checkout(root: Path) -> None:
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    _write(root / "bench" / "configs" / "pair-tiny.json", TINY_PAIR)
    _write(root / "bench" / "traffic" / "pair-closed.json",
           {"loop": "closed", "clients": 1, "check_pairs": 1})
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}  # rehearsal only
    _write(root / "bench" / "peaks.json", peaks)
    per_layer = [{"name": n, "unit": "ms", "better": "lower", "source": src, "layer": layer,
                  "moves": "pair_ms", "workloads": [CELL]}
                 for n, (src, layer) in SOURCES.items()]
    _write(root / "BENCHMARK.json", {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 1,
        "configs": [],
        "workloads": [{"name": CELL, "config": "pair-tiny", "traffic": "pair-closed",
                       "chips": 1, "why": "rehearsal"}],
        "end_to_end": [
            {"name": "pair_ms", "unit": "ms", "better": "lower", "bound": 0.25,
             "source": "host_clock", "workloads": [CELL]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
             "source": "host_clock"}],
        "per_layer": per_layer})


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny cell: its last line, its window line and
    the ``observed`` dictionary the readers were handed."""
    from repro import obs

    root = tmp_path_factory.mktemp("checkout")
    _checkout(root)
    spec = importlib.util.spec_from_file_location("bench_run_scopes", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    seen = {}
    real = mod.per_layer_metrics

    def keep(spec_, observed):
        seen["observed"] = observed
        return real(spec_, observed)

    obs.xla.clear()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod, "use_compile_cache", lambda: None)
        mp.setattr(mod, "require_chip", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": chips})
        mp.setattr(mod, "require_native_kernels", lambda: None)
        mp.setattr(mod, "per_layer_metrics", keep)
        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = mod.main(["--workload", CELL, "--seed", str(2**31 + 13), "--seconds", "0.4",
                           "--trace", "1"])
    assert rc == 0, err.getvalue()
    lines = [json.loads(x) for x in out.getvalue().strip().splitlines()]
    window = next(x for x in lines if x.get("phase") == "window")
    layers = str(root / "bench" / "layers")
    if layers not in sys.path:
        sys.path.insert(0, layers)
    yield {"result": lines[-1], "window": window, "observed": seen["observed"], "root": root}
    obs.xla.clear()


def _reader(root: Path, name: str):
    spec = importlib.util.spec_from_file_location(f"scopes_{name.replace('.', '_')}",
                                                  root / "bench" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_run_reports_the_four_metrics(traced):
    res = traced["result"]
    assert res["correct"] is True, res["checks"]
    assert set(METRICS) <= set(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in METRICS)
    assert (traced["window"]["compiles_in_window"], traced["window"]["traces_in_window"]) == (0, 0)


def test_scoped_device_time_is_part_of_busy_time(traced):
    """The scopes split the program's device time: lower bound and
    selection are parts of the scoped whole, and each reading is that
    scope's self time over the distances."""
    import _scopes

    obs_ = traced["observed"]
    n = len(obs_["work"]["n_sel"])
    parts = {s: _scopes.scope_seconds(obs_, s) for s in
             ("prohd.select", "prohd.nn_scan", "prohd.bound", "prohd.lower_bound")}
    assert all(v > 0 for v in parts.values()), parts
    assert sum(parts.values()) <= obs_["trace"].busy_s * (1 + 1e-9)
    got = traced["result"]["metrics"]
    assert got["lower_bound_ms"]["value"] == pytest.approx(1e3 * parts["prohd.lower_bound"] / n)
    assert got["selection_ms"]["value"] == pytest.approx(1e3 * parts["prohd.select"] / n)


def test_clock_places_each_validate_inside_its_call(traced):
    import _clock

    obs_ = traced["observed"]
    offs = _clock.offsets_ns(obs_)
    assert offs and max(offs) - min(offs) < 1e6
    marks = [(s, e) for name, s, e in obs_["trace"].spans if name == _clock.MARK]
    validates = _clock.intervals(obs_, "hd.validate")
    assert len(marks) == len(validates) == len(offs)
    for (ms, me), (vs, ve) in zip(marks, validates):
        assert ms <= vs < ve <= me


def test_a_wrong_pairing_reads_nothing(traced):
    obs_ = traced["observed"]
    calls = [s for s in obs_["spans"] if s["name"] == "hd.set_distance"]
    assert len(calls) >= 2
    rest = [s for s in obs_["spans"] if s["name"] != "hd.set_distance"]
    shuffled = dict(obs_, spans=rest + calls[::-1])
    import _clock

    assert _clock.offset_ns(shuffled) is None
    assert _reader(traced["root"], "validate_idle_ms").read(shuffled) is None
    assert _reader(traced["root"], "validate_idle_ms").read(obs_) is not None


def test_a_program_without_spans_or_scopes_reads_nothing(traced, monkeypatch):
    """The parent program has neither the spans nor ``repro.obs.xla``: every
    reader returns ``None`` and none raises."""
    import repro.obs

    monkeypatch.delattr(repro.obs, "xla")
    monkeypatch.setitem(sys.modules, "repro.obs.xla", None)  # the import fails
    bare = dict(traced["observed"], spans=[])
    for name in METRICS:
        assert _reader(traced["root"], name).read(bare) is None, name
