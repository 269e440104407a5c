"""Closed loop: ``clients`` analysts (1 here), each asking again as soon
as the last answer is back.  The deployment is the configuration's one
graph, oriented on the device once and kept resident, and one
``TriangleCounter`` built from the traffic's ``counter`` arguments.  The
window runs answers back to back until ``seconds`` have passed; the
answer under way then finishes and counts.
"""
import importlib
import time

import jax

from bench import graphs as graph_gen
from bench.loops import Answer, Window, no_step


def _check(traffic):
    if traffic.get("clients", 1) != 1:
        raise ValueError("the closed loop drives one client")


def graphs(config, seed, step=no_step):
    with step("generate"):
        base, n_nodes = graph_gen.generate(config)
    with step("relabel"):
        edges = graph_gen.relabel(base, n_nodes, seed)
    return {config["name"]: (edges, n_nodes)}


class Deployment:
    def __init__(self, config, seed, traffic, step):
        from repro.core import TriangleCounter, prepare_oriented

        _check(traffic)
        self.traffic = traffic
        self.answer = importlib.import_module(f"bench.answers.{traffic['answer']}")
        self.graphs = graphs(config, seed, step)
        (self.name, (edges, n_nodes)), = self.graphs.items()
        with step("orient"):
            self.csr = jax.block_until_ready(prepare_oriented(edges, n_nodes))
        self.counter = TriangleCounter(**traffic["counter"])

    def warm(self):
        self.answer.answer(self.counter, self.csr)

    def run(self, seconds):
        kind = self.traffic["answer"]
        label = f"bench.answer.{kind}"
        answers, plan_s = [], []
        with jax.profiler.TraceAnnotation("bench.window"):
            start = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(label):
                    value = self.answer.answer(self.counter, self.csr)
                end = time.perf_counter()
                answers.append(Answer(self.name, kind, value, end - t0))
                plan_s.append(self.counter.last_stats.timings["plan"])
                if end - start >= seconds:
                    break
        return Window(answers, plan_s, end - start)

    def describe(self):
        stats = self.counter.last_stats
        return (f"per answer: method={stats.method} chunks={stats.n_chunks} "
                f"peak_wedge_buffer={stats.peak_wedge_buffer}")

    def close(self):
        self.csr = self.counter = None


def setup(config, seed, traffic, step):
    return Deployment(config, seed, traffic, step)
