"""Front end: 90th percentile, over online requests due in the window, of
the time from the request's due time to the driver's submit (the POST
handler hands the request to ``AsyncNodeDriver.submit_stream``).  The pump
runs each dispatch on the event loop, so this is the wait the front end
adds before the request reaches an engine.  Moves ``ttft_p90_ms``."""
import numpy as np


def read(run):
    lags = [run.intake[r.rid] - r.due for r in run.online
            if r.in_window and r.rid in run.intake]
    if not lags:
        return None
    return 1e3 * float(np.percentile(lags, 90))
