"""What decides ``correct``: the served path against the plain reference,
the control that has to fail, and faults planted under a whole run.

The runs here skip the look for a chip (``harness.run_cell`` is called
directly) and use reduced widths on the CPU (``conftest.shrink``)."""
import copy
import time

import numpy as np
import pytest

import harness
import reference
from conftest import shrink

SEED = 2 ** 31 + 11


def run(cell, **kw):
    return harness.run_cell(cell, SEED, 4.0, False, t_start=time.monotonic(),
                            adapt=shrink, **kw)


def test_served_tokens_match_reference_both_architectures():
    """qwen3 online, internlm2 offline: mixed chunked prefill with decode
    rows piggybacked, and pure decode through the paged kernel."""
    res = run('qwen_on.chat_burst')
    checks = res['checks']
    assert res['correct'], checks
    # served greedily in bfloat16: each token at most rounding below the
    # reference's best
    for k in ('gap_mean.online', 'gap_mean.offline'):
        assert 0.0 <= checks[k]['value'] < checks[k]['limit'] / 2, k


@pytest.mark.parametrize('config', ['qwen3-0.6b_on.internlm2-1.8b_off'])
def test_control_is_not_correct(config):
    """The control (every weight matmul in int8) in the program's place
    fails the configuration's online limit on the mean gap, on three
    seeds.  Published
    widths, six layers, one 1000-token sequence."""
    cfg = harness.load_json(harness.BENCH / 'configs' / f'{config}.json')
    limit = cfg['limits']['gap_mean.online']
    hf = dict(cfg['online']['config'], num_hidden_layers=6,
              vocab_size=16384)
    for seed in (1, 2, 3):
        ref = reference.Reference(hf, seed)
        seq = np.random.default_rng(seed).integers(1, 16384, 1000).tolist()
        _, ctl = ref.gaps(seq[:100], seq[100:], control=True)
        assert ctl.mean() > limit, (seed, float(ctl.mean()), limit)


def _published_widths(cfg, mix):
    """The online model at its published widths (4 layers, a 16k
    vocabulary): at the reduced widths of ``shrink`` the int8 control
    reads below the limit, at published widths it does not."""
    cfg, mix = shrink(cfg, mix)
    online = harness.load_cell('qwen_on.chat_alone').config['online']
    cfg['online'] = copy.deepcopy(online)
    cfg['online']['config'].update(num_hidden_layers=4, vocab_size=16384)
    cfg['page_size'] = 16
    cfg['node'] = {'n_handles': 16, 'pages_per_handle': 8, 'max_seq': 128,
                   'prefill_chunk': 32, 'max_prefill_reqs': 4}
    for p in mix['online']:
        p['prompt'].update(median=48, min=16, max=64)
        p['output'] = {'law': 'uniform', 'low': 24, 'high': 48, 'min': 24,
                       'max': 48}
    return cfg, mix


@pytest.mark.parametrize('seed', [SEED, 5, 6])
def test_control_in_the_programs_place_is_not_correct(seed):
    """A whole run with the control's readings held to the limits through
    the harness's own comparison: ``correct`` comes out false, while the
    program's readings of the same run stay under the limit."""
    res = harness.run_cell('qwen_on.chat_alone', seed, 4.0, False,
                           t_start=time.monotonic(), adapt=_published_widths,
                           control=True)
    assert not res['correct']
    c = res['checks']['gap_mean.online']
    assert c['value'] == res['control']['gap_mean.online'] > c['limit']
    assert res['readings']['gap_mean.online'] < c['limit']


def _alter_tokens(node):
    """Fault: the online engine serves a token it did not sample (the
    second token of every request, plus one, where it is produced)."""
    eng = node.online
    flush = eng.flush_tokens
    done = set()

    def altered():
        flush()
        for rid, req in eng.requests.items():
            if rid not in done and len(req.generated) >= 2 \
                    and req.generated[1] >= 0:
                req.generated[1] = (req.generated[1] + 1) % eng.mcfg.vocab_size
                done.add(rid)
    eng.flush_tokens = altered
    step = eng.step

    def step_and_flush():
        out = step()
        altered()
        return out
    eng.step = step_and_flush


def _break_invariant(node):
    def broken():
        raise AssertionError('planted')
    node.pool.check_invariants = broken


@pytest.mark.parametrize('fault, check', [(_alter_tokens, 'gap_mean.online'),
                                          (_break_invariant,
                                           'broken_invariants')])
def test_fault_under_the_timed_path_is_not_correct(fault, check):
    res = run('qwen_on.chat_alone', on_node=fault)
    assert not res['correct']
    c = res['checks'][check]
    assert c['value'] > c['limit']
