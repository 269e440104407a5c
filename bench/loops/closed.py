"""Closed loop: ``clients`` analysts (1 here), each asking again as soon
as the last answer is back.  The window runs answers back to back until
``seconds`` have passed; the answer under way then finishes and counts.
"""
import time

import jax

from bench.loops import Window


def _check(traffic):
    if traffic.get("clients", 1) != 1:
        raise ValueError("the closed loop drives one client")


def warm(answer, counter, csr, traffic):
    _check(traffic)
    answer.answer(counter, csr)


def run(answer, counter, csr, seconds, traffic):
    _check(traffic)
    label = f"bench.answer.{traffic['answer']}"
    answers, latencies, plan_s = [], [], []
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                answers.append(answer.answer(counter, csr))
            end = time.perf_counter()
            latencies.append(end - t0)
            plan_s.append(counter.last_stats.timings["plan"])
            if end - start >= seconds:
                break
    return Window(answers, latencies, plan_s, end - start)
