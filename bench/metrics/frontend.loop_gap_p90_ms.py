"""Front end: 90th percentile, over consecutive turns of the pump in the
traced stretch with no park between them, of the time from one turn's end
to the next one's start, in ms.  That is the time the event loop spends on
intake, SSE writers and the rest while the node has work; a gap that holds
a park (the program's ``driver.park`` mark) is idleness and is left out.
Moves ``ttft_p90_ms``."""
import numpy as np

PUMP, PARK = 'driver.pump', 'driver.park'


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    marks = sorted((s, e, n) for n, s, e in run.trace.host
                   if n in (PUMP, PARK) and lo <= s and e <= hi)
    gaps = [b[0] - a[1] for a, b in zip(marks, marks[1:])
            if a[2] == b[2] == PUMP]
    if not gaps:
        return None
    return 1e-6 * float(np.percentile(gaps, 90))
