"""End-to-end arithmetic: time to first token from the due time, time per
output token, failures counted as missing; then a replay on a virtual
clock through reduced-width models, where every number is exact."""
import math

import pytest

import e2e
import harness
from conftest import shrink


def rec(due, first, last, n, status='ok', inside=True):
    r = e2e.OnlineRecord(due=due, want=n, in_window=inside)
    r.t_send, r.t_first, r.t_last = due, first, last
    r.tokens = list(range(n))
    r.status = status
    return r


def test_ttft_from_due_and_tpot():
    r = rec(10.0, 10.25, 11.25, 5)
    assert r.ttft == pytest.approx(0.25)
    assert r.tpot == pytest.approx(0.25)
    assert rec(1.0, 2.0, 2.0, 1).tpot is None


def test_failed_request_is_missing():
    r = rec(0.0, 0.1, 0.2, 3, status='failed: 1 of 3 tokens')
    assert r.ttft == math.inf and r.tpot is None
    ok = [rec(0.0, 0.1 * (i + 1), 1.0, 2) for i in range(9)]
    m = e2e.online_metrics(ok + [r])
    assert m['n'] == 10 and m['failed'] == 1
    assert m['ttft_p50_ms'] == pytest.approx(550.0)
    # the missing request sorts last: the 90th percentile interpolates
    # toward it and is no longer finite
    assert m['ttft_p90_ms'] == math.inf
    assert m['n_tpot'] == 9


def test_only_requests_due_in_the_window_count():
    rs = [rec(0.0, 0.2, 0.4, 3), rec(0.0, 9.0, 9.5, 3, inside=False)]
    m = e2e.online_metrics(rs)
    assert m['n'] == 1 and m['ttft_p90_ms'] == pytest.approx(200.0)


def test_percentile_is_numpy_linear():
    assert e2e.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert e2e.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == \
        pytest.approx(9.1)
    assert math.isnan(e2e.percentile([], 90))


def test_tokens_in_window():
    S = harness.StepSpan
    steps = [S('a', 'offline', 'mixed', 0.0, 0.9, 4),
             S('a', 'offline', 'decode', 1.0, 1.5, 8),
             S('b', 'online', 'decode', 1.0, 1.5, 8),
             S('a', 'offline', 'decode', 1.5, 2.0, 8)]
    assert e2e.tokens_in_window(steps, 'offline', 1.0, 2.0) == 8


def test_virtual_clock_replay(capsys):
    from repro.core.clock import VirtualClock
    import time

    res = harness.run_cell('qwen_on.chat_alone', 2 ** 31 + 5, 4.0, False,
                           t_start=time.monotonic(), adapt=shrink,
                           clock=VirtualClock())
    err = capsys.readouterr().err
    assert res['correct'], err
    assert res['device']['platform'] == 'cpu'
    assert set(res['metrics']) == {'ttft_p90_ms', 'ttft_p50_ms',
                                   'tpot_p90_ms', 'setup_s'}
    m = {k: v['value'] for k, v in res['metrics'].items()}
    assert 0.0 <= m['ttft_p50_ms'] <= m['ttft_p90_ms'] < math.inf
    assert m['tpot_p90_ms'] >= 0.0
    assert res['attempted'] >= 1 and res['failed'] == 0
    assert 'compilations inside the window: 0' in err
