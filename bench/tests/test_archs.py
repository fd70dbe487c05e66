"""Each served model's program config, reference and counts come from its
architecture's module (``bench/archs/<architectures[0]>.py``) and read
exactly as they did before they moved there: ``data/archs_golden.json``
holds what the benchmark gave for both models of the configuration before
the move (the program's ``ModelConfig``, every count of ``flops`` at
published widths, with ``decode_layers`` the paged-decode calls a step made
in the roofline reader, and the reference's gaps at ``conftest.SMALL``
widths, with and without the control, as float32)."""
import json
from pathlib import Path

import numpy as np
import pytest

import archs
import flops
import harness
import reference
from conftest import small_model

GOLDEN = json.loads((Path(__file__).resolve().parent / 'data'
                     / 'archs_golden.json').read_text())
CFG = harness.load_json(harness.BENCH / 'configs'
                        / 'qwen3-0.6b_on.internlm2-1.8b_off.json')
ENTRIES = {e['config']['architectures'][0]: e
           for e in [CFG['online'], *CFG['offline']]}
ARCHS = sorted(GOLDEN['models'])


def test_golden_covers_every_model_of_the_configuration():
    assert sorted(ENTRIES) == ARCHS


@pytest.mark.parametrize('arch', ARCHS)
def test_program_config_as_before(arch):
    from repro.configs import ModelConfig
    got = harness.model_config(ENTRIES[arch], CFG['page_size'])
    assert got == ModelConfig(**GOLDEN['models'][arch]['model_config'])


@pytest.mark.parametrize('arch', ARCHS)
def test_counts_as_before(arch):
    hf = ENTRIES[arch]['config']
    want = GOLDEN['models'][arch]['counts']
    live = GOLDEN['live']
    prefill = [tuple(p) for p in GOLDEN['prefill']]
    got = {
        'layer_params': flops.layer_params(hf),
        'body_params': flops.body_params(hf),
        'attn_flops_per_key': flops.attn_flops_per_key(hf),
        'unembed_flops': flops.unembed_flops(hf),
        'decode_step_flops': flops.decode_step_flops(hf, live),
        'mixed_step_flops': flops.mixed_step_flops(hf, prefill,
                                                   GOLDEN['decode_live']),
        'paged_decode_call': list(flops.paged_decode_call(hf, live)),
        'decode_layers': len(flops.paged_decode_calls(hf, live))}
    assert got == want
    assert all(type(v) is int for v in got['paged_decode_call'])
    # every layer's call reads alike, as the reader counted them before
    assert set(flops.paged_decode_calls(hf, live)) == {
        tuple(want['paged_decode_call'])}


def test_internlm2_counts_by_hand():
    """internlm2-1.8b: hidden 2048, 16 query heads and 8 KV heads of 128
    (hidden / heads), MLP 8192, 24 layers, vocabulary 92544."""
    hf = CFG['offline'][0]['config']
    layer = 2048 * (2048 + 2 * 1024) + 2048 * 2048 + 3 * 2048 * 8192
    assert flops.layer_params(hf) == layer == 62_914_560
    assert flops.body_params(hf) == 24 * layer
    assert flops.unembed_flops(hf) == 2 * 2048 * 92544
    assert flops.attn_flops_per_key(hf) == 4 * 16 * 128 * 24
    f, b = flops.paged_decode_call(hf, [100, 200])
    assert (f, b) == (4 * 16 * 128 * 300,
                      2 * 2 * 16 * 128 * 2 + 2 * 300 * 8 * 128 * 2)
    assert flops.paged_decode_calls(hf, [100, 200]) == [(f, b)] * 24


@pytest.mark.parametrize('window', [None, 1, 4, 100, 1000])
def test_attended_keys_by_brute_force(window):
    """Keys a step's queries see, with and without a window, against a
    count query by query."""
    from archs._decoder import attended_keys
    prefill, decode_live = [(0, 128), (128, 72), (300, 5)], [50, 7, 900]
    cap = window or 10 ** 9
    want = sum(min(p + 1, cap) for start, n in prefill
               for p in range(start, start + n))
    want += sum(min(n, cap) for n in decode_live)
    assert attended_keys(prefill, decode_live, window) == want


@pytest.mark.parametrize('control', [False, True])
@pytest.mark.parametrize('arch', ARCHS)
def test_reference_gaps_as_before(arch, control):
    rec = GOLDEN['models'][arch]
    ref = reference.Reference(small_model(ENTRIES[arch])['config'],
                              GOLDEN['seed'])
    gap, ctl = ref.gaps(rec['prompt'], rec['served'], control=control)
    assert gap.dtype == ctl.dtype == np.float32
    want_gap, want_ctl = (rec['gaps_with_control'] if control
                          else (rec['gaps'], rec['gaps']))
    np.testing.assert_array_equal(gap, np.asarray(want_gap, np.float32))
    np.testing.assert_array_equal(ctl, np.asarray(want_ctl, np.float32))


def test_unknown_architecture_names_the_file_to_add():
    hf = dict(CFG['online']['config'], architectures=['NoSuchForCausalLM'])
    for call in (lambda: harness.model_config({'model': 'x', 'config': hf},
                                              16),
                 lambda: reference.Reference(hf, 0),
                 lambda: flops.body_params(hf)):
        with pytest.raises(KeyError, match='bench/archs/NoSuchForCausalLM.py'):
            call()
    with pytest.raises(KeyError):
        archs.module('../configs/x')
