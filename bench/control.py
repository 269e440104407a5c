"""Read the control of a cell: an answer that breaks the configuration's
guarantee, put where the program's answer goes, compared as a run
compares the program's.  Every reading has to fail its limit.

    python3 -m bench.control --workload kron17.count --seeds 11 12 13

Each seed gives the cell's graphs relabelled as a run relabels them
(the loop's ``graphs``); on each graph, each answer kind of the traffic
mix has its ``control`` (``bench/answers/``) answer once and the
reference once, and each number compared is the largest over them.  One
JSON line per seed.  The benchmark's runs never call this;
``bench/tests/test_controls.py`` does at a small size.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

from bench import run as harness


def kinds(traffic: dict) -> list:
    """The answer kinds a traffic mix asks for."""
    if "mix" in traffic:
        return [k for k, share in traffic["mix"].items() if share > 0]
    return [traffic["answer"]]


def read_controls(spec: dict, workload: str, seeds, *, config: dict | None = None,
                  traffic: dict | None = None, require_tpu: bool = True) -> list:
    cell = harness.by_name(spec["workloads"], workload)
    config, traffic = harness.cell_files(spec, cell, config, traffic)
    harness.devices(cell["chips"], require_tpu)
    harness.use_compile_cache()
    loop = importlib.import_module(f"bench.loops.{traffic['loop']}")
    readings = []
    for seed in seeds:
        control_s, compared = 0.0, {}
        for edges, n_nodes in loop.graphs(config, seed).values():
            for kind in kinds(traffic):
                answer = importlib.import_module(f"bench.answers.{kind}")
                t0 = time.perf_counter()
                value = answer.control(edges, n_nodes, seed, traffic["counter"])
                control_s += time.perf_counter() - t0
                _, more = answer.compare([value], answer.reference(edges, n_nodes))
                compared = harness.merge(compared, more)
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
