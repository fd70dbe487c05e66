"""The trace reduction, on a small profiler trace recorded on one TPU v5e
chip by ``record_trace.py``: qwen3-0.6b at published widths, two mixed and
five pure-decode steps under the benchmark's spans."""
import json
import math
from pathlib import Path

import pytest

import devtrace
import flops
import harness

DATA = Path(__file__).resolve().parent / 'data'


@pytest.fixture(scope='module')
def trace():
    return devtrace.load(str(DATA / 'decode_trace.xplane.pb'))


@pytest.fixture(scope='module')
def recorded():
    return json.loads((DATA / 'decode_trace.json').read_text())


def test_window_and_idle_share(trace):
    assert 0.05 < trace.window_s < 0.2
    busy = devtrace.busy_s(trace)
    assert 0.0 < busy < trace.window_s
    assert devtrace.idle_share(trace) == pytest.approx(
        1.0 - busy / trace.window_s)
    # idle share by hand: the union of op intervals on the one device
    names, st, en = trace.ops()
    lo, hi = trace.window
    merged = devtrace._union(st, en, lo, hi)
    assert all(a < b for a, b in merged)
    assert all(b1 < a2 for (_, b1), (a2, _) in zip(merged, merged[1:]))
    assert sum(b - a for a, b in merged) * 1e-9 == pytest.approx(busy)


def test_kernel_found_by_name(trace, recorded):
    read = harness.metric_reader('paged_attention_decode_roofline')
    calls, device_s = devtrace.kernel_time(trace, read.__globals__['is_kernel'])
    decode_steps = [s for s in recorded['steps'] if s['kind'] == 'decode']
    layers = recorded['model']['num_hidden_layers']
    assert calls == layers * len(decode_steps) == 140
    assert device_s > 0
    top = devtrace.top_ops(trace)
    assert top[0][0].startswith('%paged_attention')
    assert top[0][1] == pytest.approx(device_s)


def test_idle_gaps_named_by_host_span(trace):
    gaps = devtrace.idle_by_host(trace)
    labels = {g[0] for g in gaps}
    assert labels <= {'engine.step:online:qwen3-0.6b', 'runtime.tick',
                      devtrace.IDLE_OUTSIDE_SPANS}
    total = sum(g[1] for g in gaps)
    assert total == pytest.approx(trace.window_s - devtrace.busy_s(trace))


def _run_with(trace, recorded):
    run = harness.Run(None, 1.0)
    run.trace = trace
    run.traced = (0.0, math.inf)
    run.peaks = harness.load_json(harness.BENCH / 'peaks.json')['TPU v5 lite']
    run.engines = {'online': recorded['model']}
    t = 0.0
    for s in recorded['steps']:
        run.steps.append(harness.StepSpan(
            'online', 'online', s['kind'], t, t + s['ms'] * 1e-3, 0,
            tuple(map(tuple, s['prefill'])), tuple(s['live'])))
        t += 1.0
    return run


def test_roofline_share_by_hand(trace, recorded):
    run = _run_with(trace, recorded)
    got = harness.metric_reader('paged_attention_decode_roofline')(run)
    hf, pk = recorded['model'], run.peaks
    ideal = 0.0
    for s in recorded['steps']:
        if s['kind'] == 'decode':
            f, b = flops.paged_decode_call(hf, s['live'])
            ideal += hf['num_hidden_layers'] * max(
                f / pk['bf16_flops_per_s'], b / pk['hbm_bytes_per_s'])
    _, device_s = devtrace.kernel_time(
        trace, lambda n: n.startswith('%paged_attention'))
    assert got == pytest.approx(100.0 * ideal / device_s)
    assert 0.0 < got < 100.0


def test_missing_kernel_reads_nothing_not_zero(trace, recorded):
    run = _run_with(trace, recorded)
    devs = {k: ([n.replace('%paged_attention', '%renamed') for n in v[0]],
                v[1], v[2]) for k, v in trace.devices.items()}
    run.trace = devtrace.Trace(devs, trace.host, trace.window)
    assert harness.metric_reader('paged_attention_decode_roofline')(run) is None
    run.trace = None
    assert harness.metric_reader('device.idle_share')(run) is None


def test_unmarked_trace_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        devtrace.find_xplane(str(tmp_path))
