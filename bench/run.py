#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, driver and per-layer readers are found by name under
``bench/``:

    bench/configs/<config>.json   sizes, guarantees, limits, driver name
    bench/traffic/<traffic>.json  loop, rate or clients, mode, k, ε, pool
    bench/drivers/<driver>.py     builds the data from the seed, warms up,
                                  drives the window, checks the answers
    bench/layers/<metric>.py      ``read(observed)`` for one per-layer metric

The run fails, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the Pallas kernels would run in interpret
mode.  With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and ``repro.obs``
tracing, and the result carries its per-layer metrics, ``busy_s``,
``window_s`` and a ``breakdown``.  Every run checks its answers against the
plain reference once the window has closed.  The last line of standard
output is the result, and its last key, ``checks``, holds each compared
number beside its limit; the same numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up runs from here to the window

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# Events that mean a program was compiled or fetched from the persistent
# cache: neither may happen inside the window.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class BenchError(Exception):
    """The run cannot produce a result."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one of the benchmark's files by path."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench_" + path.parent.name + "_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(bench: dict, workload: str) -> dict:
    """The cell's entry with its configuration, traffic and the metrics it
    reports, read from the files named in ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": load_json(BENCH / "configs" / f"{cell['config']}.json"),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def require_chip(chips: int) -> dict:
    """The device facts, or a failure where JAX finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (first device is {devs[0].platform!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_native_kernels() -> None:
    from repro.kernels.hausdorff.ops import _default_interpret

    if _default_interpret():
        raise BenchError("the Pallas kernels would run in interpret mode")


def use_compile_cache() -> None:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program however
    quick to compile, so that only a cell's first run in a checkout
    compiles.  Without eviction: a size limit makes every read and write
    take the cache's file lock and scan the directory, which the
    retrieval cells' thousands of small programs turn into minutes."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts compiles and traces while ``active``."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _secs, **_kw):
        if self.active:
            self.compiles += name in COMPILE_EVENTS
            self.traces += name == TRACE_EVENT


class Harness:
    """What a driver is handed: the cell's files and seed, and the window."""

    def __init__(self, args, spec: dict, device: dict):
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.cell = spec["cell"]
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.device = device
        self.counter = CompileCounter()
        self.setup_s = None
        self.peak_bytes = None
        self.spans: list[dict] = []
        self.trace_summary = None

    def note(self, phase: str, **facts) -> None:
        """One JSON line of set-up facts on standard output, as it happens."""
        print(json.dumps({"phase": phase, "t_s": time.monotonic() - T_START, **facts},
                         default=str), flush=True)

    def expect(self, what: str, got, want) -> None:
        """Fail the run where the program resolved another backend."""
        if got != want:
            raise BenchError(f"{what} resolved {got!r}, not {want!r}")

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens; with
        ``--trace 1`` it runs under the profiler and ``repro.obs``."""
        import jax

        from repro import obs

        self.setup_s = time.monotonic() - T_START
        tmp = None
        if self.trace:
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            obs.drain()
            obs.enable(xla=True)
            jax.profiler.start_trace(tmp)
        self.counter.active = True
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.counter.active = False
            if self.trace:
                jax.profiler.stop_trace()
                obs.disable()
                self.spans = [e for e in obs.drain() if e["type"] == "span"]
        self.peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[: self.cell["chips"]])
        if tmp is not None:
            from trace_reduce import find_xplane, reduce

            try:
                self.trace_summary = reduce(find_xplane(tmp))
            finally:
                shutil.rmtree(tmp, ignore_errors=True)


def per_layer_metrics(spec: dict, observed: dict) -> dict:
    out = {}
    for m in spec["per_layer"]:
        value = load_module(BENCH / "layers" / f"{m['name']}.py").read(observed)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args) -> dict:
    """Drive one run; the result's dictionary, with ``checks`` last."""
    spec = cell_spec(load_json(ROOT / "BENCHMARK.json"), args.workload)
    device = require_chip(spec["cell"]["chips"])
    for p in (ROOT / "src", BENCH, BENCH / "layers"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    require_native_kernels()
    use_compile_cache()
    h = Harness(args, spec, device)
    driver = load_module(BENCH / "drivers" / f"{spec['config']['driver']}.py")
    out = driver.run(h)

    metrics = {}
    if h.trace:
        observed = dict(out["observed"], spans=h.spans, trace=h.trace_summary,
                        peaks=load_json(BENCH / "peaks.json")["devices"].get(device["kind"]))
        if observed["peaks"] is None:
            raise BenchError(f"no peaks for {device['kind']!r} in bench/peaks.json")
        metrics = per_layer_metrics(spec, observed)
    else:
        values = dict(out["metrics"], setup_s=h.setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=int(h.peak_bytes))
    result = {"correct": None, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics, "device": dev}
    if h.trace:
        ts = h.trace_summary
        dev.update(busy_s=ts.busy_s, window_s=ts.window_s)
        result["breakdown"] = ts.breakdown()
    window = {"phase": "window", "compiles_in_window": h.counter.compiles,
              "traces_in_window": h.counter.traces,
              "memory_peak_bytes": int(h.peak_bytes), "setup_s": h.setup_s,
              **out.get("facts", {})}
    print(json.dumps(window, default=float), flush=True)
    checks = {name: {"value": float(v), "limit": float(lim)}
              for name, (v, lim) in out["checks"].items()}
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        print(f"bench: FAILED: {e}", file=sys.stderr)
        return 1
    except Exception:  # noqa: BLE001 - a run that breaks prints no result
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
