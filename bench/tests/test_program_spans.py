"""The readers of the program's own spans and counters, on hand-built
traces and counters; each reads nothing (``None``) where the program has
no such span or counter, as in the recorded trace, which holds only the
benchmark's wrappers."""
from pathlib import Path

import numpy as np
import pytest

import devtrace
import harness

US = 1000   # ns
DATA = Path(__file__).resolve().parent / 'data'
SPAN_READERS = ('frontend.pump_turn_p90_ms', 'frontend.loop_gap_p90_ms',
                'engine.online_step_idle_ms')
COUNTER_READERS = ('control.online_queue_wait_ms', 'engine.online_prefill_ms')


def read(metric, run):
    return harness.metric_reader(metric)(run)


def make_trace(host, ops=(), window=(0, 1000 * US)):
    names = [f'%fusion.{i}' for i in range(len(ops))]
    starts = np.asarray([s for s, _ in ops], np.int64)
    ends = np.asarray([e for _, e in ops], np.int64)
    return devtrace.Trace({'/device:TPU:0': (names, starts, ends)},
                          sorted(host, key=lambda h: h[1]), window)


def run_with(trace=None, counters=None):
    run = harness.Run(cell=None, seconds=10.0)
    run.trace = trace
    run.counters = counters or {}
    return run


def spans(*items):
    """(name, start µs, end µs) → the trace's (name, start ns, end ns)."""
    return [(n, s * US, e * US) for n, s, e in items]


def step(engine, t, lengths):
    """One step's five phase spans, back to back from ``t`` (µs)."""
    out = []
    for phase, d in zip(('schedule', 'stage', 'launch', 'sync', 'commit'),
                        lengths):
        out.append((f'engine.step.{phase}:{engine}', t, t + d))
        t += d
    return out


# -- front end ---------------------------------------------------------------
PUMP_TRACE = spans(('driver.pump', 0, 10), ('driver.pump', 12, 30),
                   ('driver.pump', 40, 45), ('driver.park', 50, 50),
                   ('driver.pump', 100, 130),
                   ('driver.flush', 20, 25),        # a wrapper inside a turn
                   ('driver.pump', 990, 1010))      # ends past the window


def test_pump_turn_p90_by_hand():
    got = read('frontend.pump_turn_p90_ms', run_with(make_trace(PUMP_TRACE)))
    assert got == pytest.approx(1e-3 * np.percentile([10, 18, 5, 30], 90))


def test_loop_gap_p90_leaves_out_gaps_that_hold_a_park():
    got = read('frontend.loop_gap_p90_ms', run_with(make_trace(PUMP_TRACE)))
    # 0-10 → 12-30 → 40-45 → park → 100-130: gaps 2 and 10, not 55
    assert got == pytest.approx(1e-3 * np.percentile([2, 10], 90))


def test_loop_gap_needs_two_turns_in_a_row():
    one = spans(('driver.pump', 0, 10), ('driver.park', 20, 20),
                ('driver.pump', 30, 40))
    assert read('frontend.loop_gap_p90_ms', run_with(make_trace(one))) is None


# -- engine: device idle inside the online step ------------------------------
def test_online_step_idle_by_hand():
    host = (step('online:qwen3-0.6b', 50, (70, 30, 10, 50, 40))
            + step('online:qwen3-0.6b', 600, (10, 30, 10, 50, 20))
            # the benchmark's wrapper and the offline engine: not read
            + [('engine.step:online:qwen3-0.6b', 40, 260),
               ('engine.step.launch:offline0:internlm2-1.8b', 300, 400)]
            # a step whose launch ends past the window
            + step('online:qwen3-0.6b', 985, (5, 5, 10, 10, 10)))
    ops = [(100 * US, 200 * US), (645 * US, 700 * US), (990 * US, 995 * US)]
    got = read('engine.online_step_idle_ms',
               run_with(make_trace(spans(*host), ops)))
    # step 1 spans 50-250, busy 100-200: idle 100 µs; step 2 spans
    # 600-720, busy 645-700: idle 65 µs; the last step's schedule and
    # stage lie in the window, 985-995, busy 990-995: idle 5 µs; two
    # launches in the window
    assert got == pytest.approx(1e-3 * (100 + 65 + 5) / 2)


def test_online_step_idle_without_online_launches():
    host = spans(('engine.step.launch:offline0:x', 10, 20),
                 ('engine.step.schedule:online:x', 30, 40))
    assert read('engine.online_step_idle_ms',
                run_with(make_trace(host, [(0, 5 * US)]))) is None


# -- what the program does not have, or a run without a trace ---------------
@pytest.mark.parametrize('metric', SPAN_READERS)
def test_span_readers_read_nothing_without_program_spans(metric):
    assert read(metric, run_with(None)) is None
    recorded = devtrace.load(str(DATA / 'decode_trace.xplane.pb'))
    assert read(metric, run_with(recorded)) is None


# -- control plane and engine: the online engine's counters ------------------
def counters(w0, w1, off=(100.0, 100)):
    def stats(qw, q, pf, p):
        return {'queue_wait_s': qw, 'queued': q, 'prefill_s': pf,
                'prefilled': p, 'steps': 0}
    big = stats(off[0], off[1], off[0], off[1])
    return {'w0': {'online:qwen3-0.6b': stats(*w0),
                   'offline0:internlm2-1.8b': big},
            'w1': {'online:qwen3-0.6b': stats(*w1),
                   'offline0:internlm2-1.8b': dict(big, queued=off[1] * 2,
                                                   prefilled=off[1] * 2)}}


def test_online_queue_wait_and_prefill_by_hand():
    run = run_with(counters=counters((1.0, 10, 2.0, 9), (2.5, 13, 2.6, 11)))
    assert read('control.online_queue_wait_ms', run) == pytest.approx(500.0)
    assert read('engine.online_prefill_ms', run) == pytest.approx(300.0)


@pytest.mark.parametrize('metric', COUNTER_READERS)
def test_counter_readers_read_nothing(metric):
    # no request reached the counted point in the window
    same = counters((1.0, 10, 2.0, 9), (1.0, 10, 2.0, 9))
    assert read(metric, run_with(counters=same)) is None
    # counters without the fields (a program that lacks them)
    old = {'w0': {'online:q': {'steps': 1}}, 'w1': {'online:q': {'steps': 5}}}
    assert read(metric, run_with(counters=old)) is None
    assert read(metric, run_with()) is None
