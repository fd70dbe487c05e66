"""Front end: 90th percentile of the length of the pump's turns in the
traced stretch, in ms.  A turn (the program's ``driver.pump`` span: the
node's steps, the stream deltas and the batch polls) holds the event loop,
so a request that arrives during one is taken in only after it.  Moves
``ttft_p90_ms``."""
import numpy as np

PUMP = 'driver.pump'


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    turns = [e - s for n, s, e in run.trace.host
             if n == PUMP and lo <= s and e <= hi]
    if not turns:
        return None
    return 1e-6 * float(np.percentile(turns, 90))
