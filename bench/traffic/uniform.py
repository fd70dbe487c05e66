"""Uniform whole lengths from ``low`` to ``high``, both included."""


def draw(p, n, rng):
    return rng.integers(p['low'], p['high'] + 1, n)
