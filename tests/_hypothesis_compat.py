"""Fallback so property tests collect (and run) without `hypothesis`.

The pinned environment ships hypothesis (requirements-ci.txt), and then it
is re-exported untouched.  On a host without it, a bare `from hypothesis
import ...` would abort collection of the whole module; there `given` /
`settings` / `st` degrade to a tiny seeded-random sampler: each property
test runs against a deterministic batch of random examples drawn from the
same strategy shapes.  That is weaker than real shrinking-and-database
hypothesis, but it keeps every property assertion exercised instead of
skipping the module wholesale.

Only the strategy surface this repo uses is implemented: `st.integers`,
`st.floats`, `st.booleans`, `st.sampled_from`, and (nested) `st.lists`.
"""

from __future__ import annotations

try:  # pragma: no cover - exercised only when hypothesis is installed
    from hypothesis import given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    import functools
    import random

    HAVE_HYPOTHESIS = False
    _DEFAULT_EXAMPLES = 10
    _MAX_EXAMPLES_CAP = 25  # keep the fallback fast; hypothesis-proper sweeps more

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example(self, rng: random.Random):
            return self._draw(rng)

    class _Strategies:
        @staticmethod
        def integers(min_value=0, max_value=2**31 - 1):
            return _Strategy(lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def floats(min_value=0.0, max_value=1.0, **_kw):
            return _Strategy(lambda rng: rng.uniform(min_value, max_value))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.getrandbits(1)))

        @staticmethod
        def sampled_from(elements):
            elements = list(elements)
            return _Strategy(lambda rng: rng.choice(elements))

        @staticmethod
        def lists(elements, min_size=0, max_size=10, **_kw):
            def draw(rng):
                n = rng.randint(min_size, max_size)
                return [elements.example(rng) for _ in range(n)]

            return _Strategy(draw)

    st = _Strategies()

    def settings(*, max_examples=_DEFAULT_EXAMPLES, **_kw):
        def deco(fn):
            fn._max_examples = max_examples
            return fn

        return deco

    def given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                n = min(
                    getattr(wrapper, "_max_examples", _DEFAULT_EXAMPLES),
                    _MAX_EXAMPLES_CAP,
                )
                rng = random.Random(0x9137)
                for _ in range(n):
                    drawn = {k: s.example(rng) for k, s in strategies.items()}
                    fn(*args, **drawn, **kwargs)

            # hide the strategy-drawn parameters from pytest's fixture
            # resolver (hypothesis-proper does the same)
            import inspect

            sig = inspect.signature(fn)
            wrapper.__signature__ = sig.replace(parameters=[
                p for name, p in sig.parameters.items()
                if name not in strategies
            ])
            return wrapper

        return deco
