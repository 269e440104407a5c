"""The chip benchmark of the triangle-counting system.

``BENCHMARK.json`` at the checkout root names the cells and metrics; this
package holds everything else, each part found by its name:

- ``run.py``: one run of one cell (``python3 -m bench.run --help``);
- ``configs/<config>.json``: a deployment, its source, its cuts and its
  small stand-in for the self-tests (``small``);
- ``graphs/<graph>.py``: a graph family's generator;
- ``traffic/<traffic>.json``: a traffic mix, naming its loop and answer kinds;
- ``loops/<loop>.py``: an arrival pattern and the deployment it drives;
  ``answers/<answer>.py``: a kind of answer, its reference, comparison
  and control;
- ``metrics/<metric>.py``: how one metric is read from a run;
- ``reference.py``, ``work.py``, ``trace.py``, ``peaks.json``: the
  yardstick: the plain reference, the bytes and compares of an answer,
  the trace reduction and the chip's published peaks;
- ``control.py``: readings of each cell's control;
- ``tests/``: the self-tests (``python -m pytest bench/tests``, CPU).
"""
