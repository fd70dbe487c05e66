"""Engine: mean time of an online request's prefill, from the launch of
its first dispatch to its first token, over the requests whose first token
came in the window, in ms.  Read from the online engine's ``EngineStats``
counters (``prefill_s``, ``prefilled``) at the window's edges.  Moves
``ttft_p90_ms``."""


def read(run):
    w0, w1 = run.counters.get('w0', {}), run.counters.get('w1', {})
    for label, end in w1.items():
        if label.startswith('online') and 'prefill_s' in end:
            n = end['prefilled'] - w0[label]['prefilled']
            if n > 0:
                s = end['prefill_s'] - w0[label]['prefill_s']
                return 1e3 * s / n
    return None
