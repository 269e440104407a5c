"""One module per arrival pattern, found by a traffic mix's ``loop`` name.

Each has ``warm(answer, counter, csr, traffic)``, which runs every shape
the window will use, and ``run(answer, counter, csr, seconds, traffic)``,
which measures for ``seconds`` and returns a ``Window``.
"""
import dataclasses


@dataclasses.dataclass
class Window:
    answers: list        # what each answer returned, in order
    latencies: list      # seconds each answer took
    plan_s: list         # the engine's host planning seconds of each answer
    window_s: float      # first answer's start to last answer's end
