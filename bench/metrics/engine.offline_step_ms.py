"""Engine: mean host time of the offline engines' steps in the window,
mixed (chunked prefill with piggybacked decode) and pure decode alike,
device time included.  An online request that arrives during one waits
for it to end, so it moves ``ttft_p90_ms``."""
import numpy as np


def read(run):
    d = [s.t1 - s.t0 for s in run.steps_in(run.w0, run.w1, klass='offline')]
    return 1e3 * float(np.mean(d)) if d else None
