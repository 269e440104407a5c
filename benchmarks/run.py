# One function per paper table. Print ``name,us_per_call,derived`` CSV and
# write one machine-readable BENCH_<suite>.json artifact per suite run.
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache
from repro.obs import env_fingerprint

from . import (
    ablations,
    analytics,
    compression,
    engine_chunking,
    fig1_scaling,
    ingest,
    kernel_micro,
    multidevice,
    section5_approx,
    serving,
    streaming,
    table1_runtime,
    table2_roofline,
)
from .common import emit

SUITES = {
    "table1": table1_runtime.run,      # Table I  — runtimes + speedups
    "table2": table2_roofline.run,     # Table II — kernel profiling/roofline
    "fig1": fig1_scaling.run,          # Fig. 1   — Kronecker scaling
    "ablations": ablations.run,        # §III-D   — optimization ablations
    "multidevice": multidevice.run,    # §III-E   — multi-device + Amdahl
    "section5": section5_approx.run,   # §V       — exact vs DOULION
    "kernels": kernel_micro.run,       # Pallas kernel micro-sweeps
    "chunking": engine_chunking.run,   # engine — memory-bounded partitioning
    "serving": serving.run,            # multi-tenant service: batching, snapshots
    "streaming": streaming.run,        # incremental updates vs full recount
    "ingest": ingest.run,              # out-of-core parse/canonicalize/cache
    "compression": compression.run,    # .tricsrz ratio / warm load / locality
    "analytics": analytics.run,        # support / k-truss / clustering
}

BENCH_SCHEMA = "repro-bench-v1"


def write_bench_json(out_dir: str, suite: str, rows, wall_s: float,
                     quick: bool) -> str:
    """Persist one suite's rows as a diffable BENCH_<suite>.json artifact."""
    payload = {
        "schema": BENCH_SCHEMA,
        "suite": suite,
        "quick": quick,
        "wall_s": wall_s,
        "rows": [
            {"name": name, "us_per_call": float(us), "derived": str(derived)}
            for name, us, derived in rows
        ],
        "env": env_fingerprint(),
    }
    path = os.path.join(out_dir, f"BENCH_{suite}.json")
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(SUITES), default=None,
                    help="run a single suite (historical spelling of --suite)")
    ap.add_argument("--suite", action="append", choices=sorted(SUITES),
                    default=None, metavar="NAME",
                    help="run this suite (repeatable; default: all)")
    ap.add_argument("--out-dir", default=".", metavar="DIR",
                    help="where BENCH_<suite>.json artifacts land "
                         "(default: current directory)")
    ap.add_argument("--no-artifacts", action="store_true",
                    help="CSV on stdout only, skip the BENCH_*.json files")
    ap.add_argument("--quick", action="store_true",
                    help="shrunken inputs for CI smoke (suites that honor "
                         "benchmarks.common.quick — smaller graphs, fewer "
                         "sweep points)")
    args = ap.parse_args()
    enable_compile_cache()
    selected = set(args.suite or [])
    if args.only:
        selected.add(args.only)
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    if not args.no_artifacts:
        os.makedirs(args.out_dir, exist_ok=True)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in SUITES.items():
        if selected and name not in selected:
            continue
        t0 = time.perf_counter()
        try:
            rows = list(fn())
        except Exception:
            failed.append(name)
            traceback.print_exc(file=sys.stderr)
            continue
        wall_s = time.perf_counter() - t0
        emit(rows)
        if not args.no_artifacts:
            path = write_bench_json(args.out_dir, name, rows, wall_s, args.quick)
            print(f"wrote {path} ({len(rows)} rows, {wall_s:.1f}s)",
                  file=sys.stderr)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
