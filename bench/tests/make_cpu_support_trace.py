"""Record ``data/cpu_support.xplane.pb``, a small trace of real engine
answers in which ``bench/tests/test_support.py`` finds the support
scatter's operations.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.tests.make_cpu_support_trace

A ``bench.window`` holds two ``bench.answer.support`` annotations, each
one per-edge support answer of a Kronecker graph (scale 8, edge factor 8,
seed 2) by the panel backend with a wedge budget of 2^12, and then one
``bench.answer.count`` of the same graph.  XLA's CPU client runs
synchronously here, so each program's operations run on the calling
thread, inside its ``ThunkExecutor::Execute`` under the
``PjitFunction(<name>)`` of the jitted function that launched them: that
is how the test names them after their program, as a TPU trace does
from its ``XLA Modules`` line.  Python frames are left out, to keep the
file small.
"""
import glob
import os
import shutil
import tempfile

import jax

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_support.xplane.pb")
SCALE, EDGE_FACTOR, SEED, BUDGET = 8, 8, 2, 1 << 12
SUPPORT_ANSWERS = 2


def graph():
    from repro.core import prepare_oriented
    from repro.graphs import kronecker_rmat

    return prepare_oriented(kronecker_rmat(SCALE, edge_factor=EDGE_FACTOR, seed=SEED))


def counter():
    from repro.core import TriangleCounter

    return TriangleCounter(method="panel", max_wedge_chunk=BUDGET)


def main():
    # before anything makes the CPU client, which reads it once
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    csr, tc = graph(), counter()
    tc.edge_support(csr)
    tc.count(csr)
    tmp = tempfile.mkdtemp()
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # program spans and XLA ops only
        with jax.profiler.trace(tmp, profiler_options=options):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(SUPPORT_ANSWERS):
                    with jax.profiler.TraceAnnotation("bench.answer.support"):
                        tc.edge_support(csr)
                with jax.profiler.TraceAnnotation("bench.answer.count"):
                    tc.count(csr)
        shutil.copy(sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                     recursive=True))[-1], OUT)
    finally:
        shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
