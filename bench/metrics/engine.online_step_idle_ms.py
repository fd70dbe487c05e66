"""Engine: device idle time inside the online engine's step phases, per
online dispatch, in ms.  Idle is the complement of the union of the op
intervals on the first device, as ``devtrace`` computes it; it is counted
where it falls inside the program's ``engine.step.<phase>:online...``
spans in the traced stretch, and divided by the number of online
``engine.step.launch`` spans there.  The host work of a step that the chip
waits on; moves ``tpot_p90_ms``."""
import numpy as np

import devtrace

PHASE = 'engine.step.'


def online_phase(name: str):
    """The phase of an online engine's step span, else None."""
    if not name.startswith(PHASE):
        return None
    phase, _, engine = name[len(PHASE):].partition(':')
    return phase if engine.startswith('online') else None


def overlap(a, b) -> int:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    spans = [(n, s, e) for n, s, e in run.trace.host
             if online_phase(n) and lo <= s and e <= hi]
    launches = sum(online_phase(n) == 'launch' for n, _, _ in spans)
    if not launches:
        return None
    _, st, en = run.trace.devices[sorted(run.trace.devices)[0]]
    busy = devtrace._union(st, en, lo, hi)
    inside = devtrace._union(np.asarray([s for _, s, _ in spans]),
                             np.asarray([e for _, _, e in spans]), lo, hi)
    idle = sum(e - s for s, e in inside) - overlap(inside, busy)
    return 1e-6 * idle / launches
