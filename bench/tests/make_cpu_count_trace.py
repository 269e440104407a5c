"""Record ``data/cpu_count.xplane.pb``, a small trace of real engine
answers that ``tests/test_bench_spans.py`` reduces.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.tests.make_cpu_count_trace

A ``bench.window`` holds two ``bench.answer.count`` annotations, each one
global count of a Kronecker graph (scale 8, edge factor 8, seed 2) by the
panel backend with a wedge budget of 2^10, so that an answer plans
several panel chunks of more than one width.  The answers are 10 ms
apart, so the device also idles outside every engine span.  Python
frames are left out, to keep the file small.
"""
import glob
import os
import shutil
import tempfile
import time

import jax

from repro.core import TriangleCounter, prepare_oriented
from repro.graphs import kronecker_rmat

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_count.xplane.pb")
SCALE, EDGE_FACTOR, SEED, BUDGET = 8, 8, 2, 1 << 10
ANSWERS, BETWEEN = 2, 0.010


def graph():
    return prepare_oriented(kronecker_rmat(SCALE, edge_factor=EDGE_FACTOR, seed=SEED))


def counter():
    return TriangleCounter(method="panel", max_wedge_chunk=BUDGET)


def main():
    csr, tc = graph(), counter()
    tc.count(csr)
    tmp = tempfile.mkdtemp()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # program spans and XLA ops only
        with jax.profiler.trace(tmp, profiler_options=options):
            with jax.profiler.TraceAnnotation("bench.window"):
                for i in range(ANSWERS):
                    with jax.profiler.TraceAnnotation("bench.answer.count"):
                        tc.count(csr)
                    if i + 1 < ANSWERS:
                        time.sleep(BETWEEN)
        shutil.copy(sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                     recursive=True))[-1], OUT)
    finally:
        shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
