"""Percent of the gathered panel slots that the intersection needs:
sum over oriented edges of (d+(u) + d+(v)) (``bench/work.py``), summed
over the window's answers on their graphs, over the ``slots`` counter of
the ``tc.engine.dispatch`` spans (2 x rows x width of every panel chunk,
padded rows included).  None where no panel chunk ran."""
from bench import spans
from bench.work import ID_BYTES


def read(run):
    s = spans.of(run)
    slots = s.span_stats.get("tc.engine.dispatch", {}).get("slots") if s else None
    if not slots:
        return None
    return 100.0 * (run.total("intersection_bytes") / ID_BYTES) / slots
