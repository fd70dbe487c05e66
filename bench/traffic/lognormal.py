"""Lognormal lengths: ``median`` and ``sigma`` of the underlying normal
(of the log), rounded to whole tokens; the mix clips to ``min``/``max``."""
import numpy as np


def draw(p, n, rng):
    x = rng.lognormal(np.log(p['median']), p['sigma'], n)
    return np.rint(x).astype(np.int64)
