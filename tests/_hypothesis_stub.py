"""Deterministic fallback for the slice of the `hypothesis` API we use.

The real `hypothesis` package is now installed where the tests run, so
the test modules import it and this stub is used only where it is
missing.  There it replays each ``@given`` test over a fixed number of
deterministically seeded random draws.  It implements
only what the test files need: ``given``, ``settings`` and the
``integers`` / ``lists`` / ``tuples`` / ``sampled_from`` / ``randoms`` /
``composite`` strategies.  No shrinking, no example database — failures
print the drawn values so they can be replayed by seed.

Unlike the real package, the stub ignores ``deadline``; property tests
that compile JAX code pass ``deadline=None`` so both behave alike.
"""
from __future__ import annotations

import random

DEFAULT_MAX_EXAMPLES = 25
_SEED_BASE = 0x5EED_BA5E


class _Strategy:
    """A strategy is just a draw function over a `random.Random`."""

    def __init__(self, draw_fn):
        self._draw_fn = draw_fn

    def draw(self, rnd: random.Random):
        return self._draw_fn(rnd)


class st:
    """Namespace mirroring ``hypothesis.strategies``."""

    @staticmethod
    def integers(min_value: int, max_value: int) -> _Strategy:
        return _Strategy(lambda rnd: rnd.randint(min_value, max_value))

    @staticmethod
    def lists(elements: _Strategy, min_size: int = 0, max_size: int = 10) -> _Strategy:
        def draw(rnd):
            size = rnd.randint(min_size, max_size)
            return [elements.draw(rnd) for _ in range(size)]

        return _Strategy(draw)

    @staticmethod
    def tuples(*elements: _Strategy) -> _Strategy:
        return _Strategy(lambda rnd: tuple(e.draw(rnd) for e in elements))

    @staticmethod
    def sampled_from(seq) -> _Strategy:
        seq = list(seq)
        return _Strategy(lambda rnd: seq[rnd.randrange(len(seq))])

    @staticmethod
    def randoms() -> _Strategy:
        return _Strategy(lambda rnd: random.Random(rnd.getrandbits(64)))

    @staticmethod
    def composite(fn):
        def make(*args, **kwargs):
            def draw_outer(rnd):
                return fn(lambda strategy: strategy.draw(rnd), *args, **kwargs)

            return _Strategy(draw_outer)

        return make


def settings(max_examples: int = DEFAULT_MAX_EXAMPLES, deadline=None, **_ignored):
    """Record ``max_examples`` on the (already ``given``-wrapped) test."""

    def deco(fn):
        fn._max_examples = max_examples
        return fn

    return deco


def given(*strategies: _Strategy):
    """Run the test once per deterministically seeded example."""

    def deco(fn):
        # Deliberately NOT functools.wraps: the wrapper must expose a
        # (*args, **kwargs) signature so pytest does not mistake the
        # strategy parameters for fixture names.
        def wrapper(*args, **kwargs):
            n = getattr(wrapper, "_max_examples", DEFAULT_MAX_EXAMPLES)
            for example in range(n):
                rnd = random.Random(_SEED_BASE ^ example)
                drawn = [s.draw(rnd) for s in strategies]
                try:
                    fn(*args, *drawn, **kwargs)
                except Exception:
                    print(f"falsifying example #{example}: {drawn!r}")
                    raise

        wrapper.__name__ = fn.__name__
        wrapper.__doc__ = fn.__doc__
        wrapper.__module__ = fn.__module__
        return wrapper

    return deco
