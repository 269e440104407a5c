"""Record ``data/cpu_window.xplane.pb``, the small trace that
``test_trace.py`` reduces.

    JAX_PLATFORMS=cpu python -m bench.tests.make_cpu_trace

A ``bench.window`` holds three ``bench.answer.count`` annotations, each
running one jitted matrix product and then sleeping 20 ms; the answers
are 10 ms apart.  So the device idles inside each answer and between
them, and the reduction has to name those gaps after the annotations.
"""
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "cpu_window.xplane.pb")
ANSWERS, ANSWER_SLEEP, BETWEEN = 3, 0.020, 0.010


def main():
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                for i in range(ANSWERS):
                    with jax.profiler.TraceAnnotation("bench.answer.count"):
                        f(x).block_until_ready()
                        time.sleep(ANSWER_SLEEP)
                    if i + 1 < ANSWERS:
                        time.sleep(BETWEEN)
        shutil.copy(sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                     recursive=True))[-1], OUT)
    finally:
        shutil.rmtree(tmp)


if __name__ == "__main__":
    main()
