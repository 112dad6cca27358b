"""Benchmark entry point (deliverable d): one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows, then a findings summary.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig1,...]
                                            [--json [PATH]]

``--json`` additionally persists every row (plus environment metadata) to a
machine-readable JSON file — ``BENCH_PR1.json`` by default — so the perf
trajectory of the repo is diffable across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

DEFAULT_JSON = "BENCH_PR1.json"

# Version of the --json payload's structure (meta/rows/findings + the
# host fingerprint).  Bump on any change a cross-PR diff tool would have
# to branch on.
BENCH_SCHEMA_VERSION = 2


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def _host_fingerprint(jax) -> dict:
    """Where these numbers came from: two runs with different fingerprints
    are not directly comparable and a diff tool should say so."""
    dev = jax.devices()[0]
    return {
        "platform": platform.platform(),
        "machine": platform.machine(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
    }


def _git_rev() -> str:
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        )
        rev = out.stdout.strip()
        if out.returncode == 0 and rev:
            dirty = subprocess.run(
                ["git", "status", "--porcelain"],
                capture_output=True, text=True, timeout=10, cwd=repo,
            )
            return rev + ("-dirty" if dirty.stdout.strip() else "")
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help="comma list: fig1,table2,fig2,fig3,fig4,fig5,phases,backends,fused,dispatch,index,index_stage2,bucket_kernel,reliability,multiquery,obs,anytime,sharded",
    )
    ap.add_argument(
        "--quick", action="store_true", help="fig1 + phases + fused only"
    )
    ap.add_argument(
        "--json",
        nargs="?",
        const=DEFAULT_JSON,
        default=None,
        metavar="PATH",
        help=f"also write rows as JSON (default path: {DEFAULT_JSON})",
    )
    args = ap.parse_args()

    import jax

    from benchmarks import tables
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    benches = {
        "fig1": tables.fig1_overall_effectiveness,
        "table2": tables.table2_sample_efficiency,
        "fig2": tables.fig2_param_sensitivity,
        "fig3": tables.fig3_dim_scalability,
        "fig4": tables.fig4_ratio_scalability,
        "fig5": tables.fig5_size_scalability,
        "phases": tables.bench_prohd_phases,
        "backends": tables.bench_backends,
        "fused": tables.bench_fused_vs_twosweep,
        "dispatch": tables.bench_dispatch_overhead,
        "index": tables.bench_index,
        "index_stage2": tables.bench_index_stage2,
        "bucket_kernel": tables.bench_bucket_kernel,
        "reliability": tables.bench_reliability,
        "multiquery": tables.bench_multiquery,
        "obs": tables.bench_obs,
        "anytime": tables.bench_anytime,
        "sharded": tables.bench_sharded,
    }
    if args.quick:
        selected = ["fig1", "phases", "fused"]
    elif args.only:
        selected = [s.strip() for s in args.only.split(",")]
    else:
        # "backends" already embeds the fused comparison; skip the
        # standalone entry so a full run doesn't execute it twice.
        selected = [n for n in benches if n != "fused"]

    all_rows: list[str] = []
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.time()
        try:
            for row in benches[name]():
                print(row, flush=True)
                all_rows.append(row)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            raise
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if tables.REPORT:
        print("\n# ==== findings ====")
        for line in tables.REPORT:
            print(f"# {line}")

    if args.json:
        payload = {
            "meta": {
                "schema": BENCH_SCHEMA_VERSION,
                "benches": selected,
                "host": _host_fingerprint(jax),
                "git_rev": _git_rev(),
                "unix_time": int(time.time()),
            },
            "rows": [_parse_row(r) for r in all_rows],
            "findings": list(tables.REPORT),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(payload['rows'])} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
