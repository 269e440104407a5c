"""Open loop: requests arrive at a ``GraphService`` on a schedule drawn
from the seed, whether or not earlier ones are back.

The deployment is one ``GraphService`` (``repro.serve``) holding the
configuration's ``tenants``: each a graph of ``bench/graphs/`` with its
parameters, relabelled from the seed, written as an edge list under the
benchmark's cache and attached by that file.  The service is built with
the traffic's ``counter`` arguments.  A tenant's vertices are those its
edge list names, as the service loads them: the generator's vertices
that have an edge, numbered in id order before the relabelling, so that
every seed gives the same vertex count and compiled shapes.

The traffic mix (``bench/traffic/<name>.json``) gives:

- ``rate``: requests per second, Poisson; with ``burst``
  (``{"on_s", "off_s", "factor"}``) the rate is ``factor`` times ``rate``
  for ``on_s`` seconds, then lower for ``off_s`` seconds, with the same
  mean;
- ``zipf_s``: the tenants' popularity, the i-th in the configuration's
  order weighted ``1 / i**zipf_s``;
- ``mix``: each answer kind (``bench/answers/``) with its share;
- ``drain_s``: how long answers are awaited once the last request is sent.

Every seed gives the same ``round(rate * seconds)`` requests: the same
gaps between arrivals (the exponential distribution's quantiles, in
operational time) and the same count of each (tenant, kind) pair, each
in an order drawn from the seed.  So seeds differ in order, not in work.

One sender thread submits each request at its scheduled time on
``time.monotonic``, the clock of the service's tickets.  A request's
latency runs from its scheduled arrival to its ticket's resolution, so
time the sender falls behind counts too.  The window runs from the first
arrival to the last answer.  A request that is refused, times out, fails,
or is not back ``drain_s`` after the last arrival counts as failed.
"""
import collections
import math
import os
import shutil
import threading
import time

import jax
import numpy as np

from bench import graphs as graph_gen
from bench.loops import Answer, Window, no_step

CACHE = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
                     ".bench_cache", "service")


def graphs(config, seed, step=no_step):
    out = {}
    for tenant in config["tenants"]:
        with step("generate"):
            base, _ = graph_gen.generate(tenant)
            # an edge list names only vertices with edges: number those
            # 0 .. n-1 in id order, so that every seed's file names all n
            ids, base = np.unique(base, return_inverse=True)
            base = base.reshape(-1, 2).astype(np.int32)
        with step("relabel"):
            out[tenant["name"]] = (graph_gen.relabel(base, len(ids), seed), len(ids))
    return out


def write_edge_list(path, edges):
    """Each undirected edge of a canonical array once, ``u v`` per line,
    as fixed-width decimal ids (a SNAP text edge list)."""
    pairs = edges[edges[:, 0] < edges[:, 1]].astype(np.int64)
    width = len(str(int(pairs.max())))
    digits = pairs[..., None] // 10 ** np.arange(width - 1, -1, -1) % 10 + ord("0")
    rows = np.empty((len(pairs), 2 * width + 2), np.uint8)
    rows[:, :width], rows[:, width + 1:-1] = digits[:, 0], digits[:, 1]
    rows[:, width], rows[:, -1] = ord(" "), ord("\n")
    with open(path, "wb") as f:
        f.write(rows.tobytes())


def _counts(n, weights):
    """``n`` split in proportion to ``weights`` by largest remainders."""
    share = n * np.asarray(weights, np.float64) / np.sum(weights)
    out = np.floor(share).astype(np.int64)
    out[np.argsort(out - share, kind="stable")[:n - out.sum()]] += 1
    return out


def _operational_to_seconds(s, rate, burst):
    """Arrival times of the points ``s`` of operational time (expected
    arrivals so far) under the traffic's rate, with or without bursts."""
    if not burst:
        return s / rate
    on, off, factor = burst["on_s"], burst["off_s"], burst["factor"]
    off_rate = rate * (on + off - factor * on) / off
    if off_rate < 0:
        raise ValueError(f"burst factor {factor} leaves no mean of {rate}/s")
    cycle, on_mass = rate * (on + off), factor * rate * on
    k, r = np.divmod(s, cycle)
    in_off = (r - on_mass) / off_rate if off_rate > 0 else np.zeros_like(r)
    return k * (on + off) + np.where(r < on_mass, r / (factor * rate), on + in_off)


def schedule(traffic, seed, tenants, seconds):
    """``[(arrival_s, tenant, kind)]`` of one run, in order of arrival."""
    n = max(1, round(traffic["rate"] * seconds))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % (1 << 64), 1]))
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    t = _operational_to_seconds(np.concatenate([[0.0], np.cumsum(gaps[:-1])]),
                                traffic["rate"], traffic.get("burst"))
    popularity = [1.0 / (i + 1) ** traffic["zipf_s"] for i in range(len(tenants))]
    kinds = [k for k, share in traffic["mix"].items() if share > 0]
    joint = [p * traffic["mix"][k] for p in popularity for k in kinds]
    pairs = np.repeat(np.arange(len(joint)), _counts(n, joint))
    pairs = rng.permutation(pairs)
    return [(float(a), tenants[j // len(kinds)], kinds[j % len(kinds)])
            for a, j in zip(t, pairs)]


def _percentile(values, q):
    ordered = sorted(values)
    return ordered[math.ceil(q * len(ordered)) - 1] if ordered else None


class Deployment:
    def __init__(self, config, seed, traffic, step):
        from repro.serve import GraphManager, GraphService

        self.seed, self.traffic = seed, traffic
        self.graphs = graphs(config, seed, step)
        shutil.rmtree(CACHE, ignore_errors=True)
        os.makedirs(CACHE)
        with step("write"):
            paths = {}
            for name, (edges, _) in self.graphs.items():
                paths[name] = os.path.join(CACHE, f"{name}.txt")
                write_edge_list(paths[name], edges)
        with step("start"):
            self.service = GraphService(GraphManager(os.path.join(CACHE, "tricsr")),
                                        **traffic["counter"])
            for name, path in paths.items():
                self.service.attach(name, path)

    def warm(self):
        for name in self.graphs:
            for kind, share in self.traffic["mix"].items():
                if share > 0:
                    self.service.submit(name, kind).result()

    def _send(self, arrivals, t0, tickets):
        for i, (at, graph, kind) in enumerate(arrivals):
            delay = t0 + at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            self.late_s.append(time.monotonic() - (t0 + at))
            try:
                tickets[i] = self.service.submit(graph, kind)
            except Exception as e:       # refused (QueueOverflow, closed): no answer
                tickets[i] = e

    def run(self, seconds):
        arrivals = schedule(self.traffic, self.seed, list(self.graphs), seconds)
        tickets = [None] * len(arrivals)
        self.late_s, self.errors = [], collections.Counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.monotonic()
            sender = threading.Thread(target=self._send, args=(arrivals, t0, tickets),
                                      name="bench-sender")
            sender.start()
            sender.join()
            deadline = time.monotonic() + self.traffic["drain_s"]
            answers, failed, end = [], 0, t0
            for (at, graph, kind), ticket in zip(arrivals, tickets):
                if isinstance(ticket, BaseException):
                    error = ticket
                else:
                    try:
                        error = ticket.exception(max(0.0, deadline - time.monotonic()))
                    except TimeoutError:
                        error = TimeoutError("not back by the end of the drain")
                if error is not None:
                    failed += 1
                    self.errors[type(error).__name__] += 1
                    continue
                answers.append(Answer(graph, kind, ticket.result(), ticket.t_done - (t0 + at)))
                end = max(end, ticket.t_done)
        if failed:
            end = max(end, time.monotonic())
        return Window(answers, [], end - t0, failed)

    def describe(self):
        counters = self.service.stats()["counters"]
        return (f"service: sender late p95={_percentile(self.late_s, 0.95)}s "
                f"max={max(self.late_s, default=None)}s; failed by cause {dict(self.errors)}; "
                f"counters {counters}")

    def close(self):
        self.service.queue.reject_pending(RuntimeError("the window is over"))
        self.service.close()
        self.service = None


def setup(config, seed, traffic, step):
    return Deployment(config, seed, traffic, step)
