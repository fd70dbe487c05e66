"""A cell that spans several chips: its config's mesh reaches the node and
the reference, the chip counts have to agree, and the readers of a step's
share count every chip.  On the host platform's four virtual devices
(``conftest``)."""
import copy
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import devtrace
import harness
import reference
from archs import _decoder
from conftest import shrink, small_model

GOLDEN = json.loads((Path(__file__).resolve().parent / 'data'
                     / 'archs_golden.json').read_text())
CONFIG = 'qwen3-0.6b_on.internlm2-1.8b_off'
CFG = harness.load_json(harness.BENCH / 'configs' / f'{CONFIG}.json')
ENTRIES = {e['config']['architectures'][0]: e
           for e in [CFG['online'], *CFG['offline']]}
ARCHS = sorted(ENTRIES)
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def four_devices():
    return harness.node_mesh({'mesh': {'model': 4}})


def declaring(monkeypatch, mesh, cfg_chips, cell_chips,
              cell='qwen_on.chat_burst'):
    """``harness.load_json`` as if the config declared ``mesh`` (None: no
    key) and ``cfg_chips``, and BENCHMARK.json gave ``cell``
    ``cell_chips``."""
    load = harness.load_json

    def patched(path):
        data = load(path)
        if Path(path).name == 'BENCHMARK.json':
            for w in data['workloads']:
                if w['name'] == cell:
                    w['chips'] = cell_chips
        elif Path(path).name == f'{CONFIG}.json':
            data['chips'] = cfg_chips
            data.pop('mesh', None)
            if mesh is not None:
                data['mesh'] = mesh
        return data
    monkeypatch.setattr(harness, 'load_json', patched)


@pytest.mark.parametrize('arch', ARCHS)
def test_weights_over_a_mesh_are_the_same_bits_split(arch):
    """The law's weights laid over four devices: the same bits as on one,
    each matrix's last axis split (every device holds a quarter of its
    bytes), each norm weight whole on every device."""
    spec = _decoder.spec_of(small_model(ENTRIES[arch])['config'])
    shapes = _decoder.param_shapes(spec)
    mesh = four_devices()
    whole = _decoder.make_weights(shapes, spec.d, 7)
    split = _decoder.make_weights(shapes, spec.d, 7, mesh)
    for name, (shape, fan_in) in shapes.items():
        a, b = whole[name], split[name]
        np.testing.assert_array_equal(np.asarray(a).view(np.uint16),
                                      np.asarray(b).view(np.uint16), name)
        shards = b.addressable_shards
        assert {s.device for s in shards} == set(mesh.devices.flat), name
        want = b.nbytes // 4 if fan_in is not None else b.nbytes
        assert [s.data.nbytes for s in shards] == [want] * 4, name


@pytest.mark.parametrize('arch', ARCHS)
def test_reference_over_a_mesh_reads_as_on_one_device(arch):
    """The served sequences' gaps, and the control's, over four devices
    against ``mesh=None``, at the tests' small widths.  A contraction over
    a split axis sums in another order, so the float32 logits, and the
    gaps taken from them, agree to rounding: 3.3e-6 at most here, in gaps
    up to about 4 (the recorded tokens are not the model's greedy ones),
    far below the limits' 0.004."""
    rec = GOLDEN['models'][arch]
    hf = small_model(ENTRIES[arch])['config']
    one = reference.Reference(hf, GOLDEN['seed'])
    four = reference.Reference(hf, GOLDEN['seed'], mesh=four_devices())
    assert len(four.w['layers/wq'].sharding.device_set) == 4
    g1, c1 = one.gaps(rec['prompt'], rec['served'], control=True)
    g4, c4 = four.gaps(rec['prompt'], rec['served'], control=True)
    np.testing.assert_allclose(g4, g1, rtol=0, atol=1e-5)
    np.testing.assert_allclose(c4, c1, rtol=0, atol=1e-5)


@pytest.mark.parametrize('mesh, cfg_chips, cell_chips', [
    ({'model': 4}, 4, 1), ({'model': 4}, 1, 1), (None, 1, 4),
    ({'data': 2, 'model': 2}, 4, 2)])
def test_chips_that_disagree_fail_at_load_cell(monkeypatch, mesh, cfg_chips,
                                               cell_chips):
    declaring(monkeypatch, mesh, cfg_chips, cell_chips)
    size = int(np.prod(list(mesh.values()))) if mesh else 1
    with pytest.raises(ValueError) as e:
        harness.load_cell('qwen_on.chat_burst')
    msg = str(e.value)
    assert f'mesh of {size} device' in msg
    assert f'chips {cfg_chips};' in msg and f'cell chips {cell_chips}' in msg


def test_chips_that_agree_load(monkeypatch):
    declaring(monkeypatch, {'data': 2, 'model': 2}, 4, 4)
    cell = harness.load_cell('qwen_on.chat_burst')
    assert cell.chips == 4 and cell.config['mesh'] == {'data': 2, 'model': 2}


def test_run_over_a_four_device_mesh_is_correct(monkeypatch):
    """A config that declares ``{"model": 4}`` runs end to end: every
    engine's parameters and each reference's weights lie over the four
    devices, and the run ends ``correct``."""
    declaring(monkeypatch, {'model': 4}, 4, 4)
    seen = {}

    def on_node(node):
        seen['params'] = {
            label: {len(x.sharding.device_set)
                    for x in jax.tree.leaves(eng.params)}
            for label, eng in node.names.items()}

    build = reference.Reference

    def recorded(hf, seed, mesh=None):
        ref = build(hf, seed, mesh=mesh)
        seen.setdefault('reference', []).append(
            {len(w.sharding.device_set) for w in ref.w.values()})
        return ref
    monkeypatch.setattr(reference, 'Reference', recorded)
    res = harness.run_cell('qwen_on.chat_burst', 2 ** 31 + 17, 4.0, False,
                           t_start=time.monotonic(), adapt=shrink,
                           on_node=on_node)
    assert res['correct'], res['checks']
    assert res['device']['count'] == 4
    assert len(seen['params']) == 2
    assert all(n == {4} for n in seen['params'].values()), seen
    assert seen['reference'] == [{4}, {4}]     # online and offline


def synthetic_run(chips, trace=None):
    run = harness.Run(cell=None, seconds=10.0, chips=chips, w0=0.0, w1=10.0,
                      peaks=PEAKS)
    off = CFG['offline'][0]['config']
    run.engines = {'online': CFG['online']['config'], 'offline0': off}
    run.steps = [
        harness.StepSpan('online', 'online', 'decode', 1.0, 1.03, 3, (),
                         (300, 41, 900)),
        harness.StepSpan('online', 'online', 'decode', 2.0, 2.031, 3, (),
                         (301, 42, 901)),
        harness.StepSpan('offline0', 'offline', 'mixed', 3.0, 3.05, 3,
                         ((0, 128), (128, 64)), (700,)),
        harness.StepSpan('offline0', 'offline', 'decode', 4.0, 4.04, 8, (),
                         (600,) * 8)]
    run.trace = trace
    run.traced = (0.0, 10.0)
    return run


@pytest.mark.parametrize('metric', ['mfu.online_decode', 'mfu.offline'])
def test_step_share_counts_every_chip(metric):
    read = harness.metric_reader(metric)
    one, four = read(synthetic_run(1)), read(synthetic_run(4))
    assert one > 0
    assert four == one / 4


def kernel_trace(devices):
    """The 80 paged-decode calls of the run's three decode steps (28 layers
    of qwen3, 24 of internlm2), 40 us of kernel time each, split evenly
    over ``devices`` devices that run them together."""
    per = 40_000 // devices
    names, starts, ends = [], [], []
    for i in range(2 * 28 + 24):
        t = 1_000_000 + 50_000 * i
        names.append(f'%paged_attention.{i} = bf16[8,16,128] custom-call()')
        starts.append(t)
        ends.append(t + per)
    ops = (names, np.asarray(starts, np.int64), np.asarray(ends, np.int64))
    return devtrace.Trace({f'/device:TPU:{d}': copy.deepcopy(ops)
                           for d in range(devices)}, [],
                          (0, 10 ** 10))


def test_roofline_reads_the_same_over_four_devices():
    """The kernel's calls split over four devices, each running a quarter
    of every call's time: the whole calls' work over the summed device
    time reads as on one device.  The breakdown's device ops are each
    device's mean, as the busy time is."""
    read = harness.metric_reader('paged_attention_decode_roofline')
    one = read(synthetic_run(1, kernel_trace(1)))
    four = read(synthetic_run(4, kernel_trace(4)))
    assert 0 < one < 100
    assert four == one
    top1, top4 = (devtrace.top_ops(kernel_trace(n))[0][1] for n in (1, 4))
    assert top4 == top1 / 4
