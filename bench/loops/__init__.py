"""One module per arrival pattern, found by a traffic mix's ``loop`` name.

A loop owns the deployment it drives.  Each module has:

- ``graphs(config, seed, step=no_step) -> {name: (edges, n_nodes)}``: the
  graphs the deployment holds, each relabelled from ``seed``, as the plain
  reference sees them (``bench/control.py`` reads them too);
- ``setup(config, seed, traffic, step)``: builds the deployment on those
  graphs, timing each set-up step under ``with step(name):``, and returns
  an object with

  - ``graphs``: the ``graphs(...)`` above;
  - ``warm()``: runs every shape the window will use;
  - ``run(seconds) -> Window``: measures for ``seconds``;
  - ``describe() -> str``: one line for the log, read after the window;
  - ``close()``: frees the program's state.
"""
import contextlib
import dataclasses


@dataclasses.dataclass
class Answer:
    graph: str           # the name of the graph asked, a key of ``graphs``
    kind: str            # the answer kind, a module of ``bench/answers/``
    value: object        # what the program returned
    latency_s: float     # seconds from the request (its scheduled arrival) to the answer


@dataclasses.dataclass
class Window:
    answers: list        # an ``Answer`` per request answered, in order of arrival
    plan_s: list         # the engine's host planning seconds of each answer, where known
    window_s: float      # first request's start to last answer's end
    failed: int = 0      # requests that got no answer: refused, timed out, or not back in time

    @property
    def latencies(self) -> list:
        return [a.latency_s for a in self.answers]


def no_step(name: str):
    """A ``step`` that times nothing, for callers outside a run."""
    return contextlib.nullcontext()
