"""Poisson arrivals at ``rps`` requests per second.

The count over the duration is one Poisson draw; given the count, the
arrival times are uniform over the duration (a Poisson process).
"""


def periods(p, duration, rng):
    return [(duration, int(rng.poisson(p['rps'] * duration)), 'all')]
