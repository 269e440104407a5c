"""The benchmark's self-tests: ``python -m pytest bench/tests`` from the
checkout root, on the CPU.  They are not among the repository's tier-1
tests (``pytest.ini`` collects ``tests/`` only)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
