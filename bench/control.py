"""Read the control of a cell: an answer that breaks the configuration's
guarantee, put where the program's answer goes, compared as a run
compares the program's.  Every reading has to fail its limit.

    python3 -m bench.control --workload kron17.count --seeds 11 12 13

Each seed gives the cell's graph relabelled as a run relabels it; the
answer kind's ``control`` (``bench/answers/``) answers once and the
reference once.  One JSON line per seed.  The benchmark's runs never
call this; ``bench/tests/test_controls.py`` does at a small size.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

from bench import run as harness


def read_controls(spec: dict, workload: str, seeds, *, config: dict | None = None,
                  require_tpu: bool = True) -> list:
    cell = harness.by_name(spec["workloads"], workload)
    if config is None:
        config = harness.load_json(os.path.join(
            harness.ROOT, harness.by_name(spec["configs"], cell["config"])["file"]))
    traffic = harness.load_json(os.path.join(harness.ROOT, "bench", "traffic",
                                             f"{cell['traffic']}.json"))
    harness.devices(cell["chips"], require_tpu)
    harness.use_compile_cache()
    from bench import graphs

    answer = importlib.import_module(f"bench.answers.{traffic['answer']}")
    base, n_nodes = graphs.generate(config)
    readings = []
    for seed in seeds:
        edges = graphs.relabel(base, n_nodes, seed)
        t0 = time.perf_counter()
        value = answer.control(edges, n_nodes, seed, traffic["counter"])
        control_s = time.perf_counter() - t0
        _, compared = answer.compare([value], answer.reference(edges, n_nodes))
        readings.append({"workload": workload, "seed": seed, "control_s": control_s,
                         "compared": {k: {"value": v, "limit": lim}
                                      for k, (v, lim) in compared.items()},
                         "fails": any(v > lim for v, lim in compared.values())})
    return readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        readings = read_controls(harness.load_spec(), args.workload, args.seeds)
    except harness.NoChip as e:
        harness.log(f"no reading: {e}")
        return harness.NO_CHIP
    for r in readings:
        print(json.dumps(r), flush=True)
    return 0 if all(r["fails"] for r in readings) else 1


if __name__ == "__main__":
    sys.exit(main())
