"""Engine: mean host time of the online engine's pure-decode steps in the
window.  The online engine samples from its logits on the host, which
waits for the device, so this includes the device time of the step.
Moves ``tpot_p90_ms``."""
import numpy as np


def read(run):
    d = [s.t1 - s.t0 for s in run.steps_in(run.w0, run.w1, klass='online',
                                           kind='decode')]
    return 1e3 * float(np.mean(d)) if d else None
