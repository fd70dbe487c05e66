"""Device: share of the traced stretch in which no op ran on the chip
(1 - union of the op intervals of the profiler trace / its length), in %.
Moves ``ttft_p90_ms``: a chip busy with offline work when an online
request arrives makes it wait; an idle one is what colocation can still
harvest."""
import devtrace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * devtrace.idle_share(run.trace)
