"""The offline per-layer readers find something to read in a window where
the offline engine only decoded, and nothing where it never ran."""
import pytest

import flops
import harness

HF = harness.load_json(harness.BENCH / 'configs' /
                       'qwen3-0.6b_on.internlm2-1.8b_off.json')
OFF = HF['offline'][0]['config']
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def make_run(steps):
    run = harness.Run(cell=None, seconds=10.0, w0=0.0, w1=10.0, peaks=PEAKS)
    run.engines = {'online': HF['online']['config'], 'offline0': OFF}
    run.steps = steps
    return run


def offline_decode(t0, live):
    return harness.StepSpan('offline0', 'offline', 'decode', t0, t0 + 0.04,
                            len(live), (), tuple(live))


def offline_mixed(t0, prefill, live):
    return harness.StepSpan('offline0', 'offline', 'mixed', t0, t0 + 0.05,
                            len(prefill) + len(live), tuple(prefill),
                            tuple(live))


@pytest.mark.parametrize('metric', ['engine.offline_step_ms', 'mfu.offline'])
def test_offline_readers_read_decode_only_windows(metric):
    read = harness.metric_reader(metric)
    decode_only = make_run([offline_decode(1.0 + i, [600] * 8)
                            for i in range(3)])
    assert read(decode_only) is not None
    assert read(make_run([])) is None


def test_offline_step_readers_by_hand():
    steps = [offline_mixed(1.0, [(0, 128), (128, 128)], [700, 701]),
             offline_decode(2.0, [600] * 8)]
    run = make_run(steps)
    assert harness.metric_reader('engine.offline_step_ms')(run) == \
        pytest.approx(45.0)
    work = (flops.mixed_step_flops(OFF, [(0, 128), (128, 128)], [700, 701])
            + flops.decode_step_flops(OFF, [600] * 8))
    assert harness.metric_reader('mfu.offline')(run) == pytest.approx(
        100.0 * work / (0.09 * 197e12))
