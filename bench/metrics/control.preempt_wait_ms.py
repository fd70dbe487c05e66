"""Control plane: mean, over online requests due while an offline dispatch
was in flight, of the time from the due time to that dispatch's end.  The
gate can only stop offline work between dispatches, so this is the
preemption latency on the device, the program in flight included.  Moves
``ttft_p90_ms``."""
import bisect

import numpy as np


def read(run):
    spans = sorted((s.t0, s.t1) for s in run.steps if s.klass == 'offline')
    starts = [a for a, _ in spans]
    waits = []
    for r in run.online:
        if not r.in_window:
            continue
        i = bisect.bisect_right(starts, r.due) - 1
        if i >= 0 and spans[i][0] <= r.due < spans[i][1]:
            waits.append(spans[i][1] - r.due)
    if not waits:
        return None
    return 1e3 * float(np.mean(waits))
