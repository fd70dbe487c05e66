"""Control plane: mean time an online request waits in the engine, from
its submit to the launch of the first dispatch that carries it, over the
requests first dispatched in the window, in ms.  Read from the online
engine's ``EngineStats`` counters (``queue_wait_s``, ``queued``) at the
window's edges.  Moves ``ttft_p90_ms``."""


def read(run):
    w0, w1 = run.counters.get('w0', {}), run.counters.get('w1', {})
    for label, end in w1.items():
        if label.startswith('online') and 'queue_wait_s' in end:
            n = end['queued'] - w0[label]['queued']
            if n > 0:
                s = end['queue_wait_s'] - w0[label]['queue_wait_s']
                return 1e3 * s / n
    return None
