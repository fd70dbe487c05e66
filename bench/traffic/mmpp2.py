"""Two-state Markov-modulated Poisson arrivals (BurstGPT, arXiv:2401.17644).

Parameters: ``on_mean_s`` / ``off_mean_s`` (exponential sojourn means) and
``on_rps`` / ``off_rps`` (Poisson rate in each state).  The states
alternate, starting ON.  Sojourns are drawn until they cover the duration
and then scaled to cover it exactly; each period's arrival count is a
Poisson draw at its state's rate.  ON periods may swap places with ON
periods, OFF with OFF.
"""


def periods(p, duration, rng):
    spans, t, on = [], 0.0, True
    while t < duration:
        length = rng.exponential(p['on_mean_s'] if on else p['off_mean_s'])
        spans.append((length, on))
        t += length
        on = not on
    scale = duration / t
    out = []
    for length, on in spans:
        length *= scale
        rate = p['on_rps'] if on else p['off_rps']
        out.append((length, int(rng.poisson(rate * length)),
                    'on' if on else 'off'))
    return out
