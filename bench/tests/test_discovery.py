"""A later cell is data: a new configuration, traffic mix, arrival kind and
per-layer metric become visible by their files and one new entry in
BENCHMARK.json, and a new architecture by its module under bench/archs/,
with no existing file edited."""
import json
import shutil
import subprocess
import sys
import textwrap

import harness

PROBE = textwrap.dedent('''
    import json, sys
    sys.path.insert(0, sys.argv[1])
    import harness, workload
    cell = harness.load_cell('tiny.steady')
    sched = workload.online_schedule(cell.mix, 10.0, 7, vocab=100)
    run = harness.Run(cell, 10.0)
    print(json.dumps({
        'chips': cell.chips,
        'online_model': cell.config['online']['model'],
        'arrivals': [round(a.t, 6) for a in sched.arrivals],
        'end_to_end': [m['name'] for m in cell.end_to_end],
        'per_layer': [m['name'] for m in cell.per_layer],
        'new_metric': harness.metric_reader('engine.steps_seen')(run)}))
''')


def test_new_files_become_visible(tmp_path):
    root = tmp_path / 'checkout'
    shutil.copytree(harness.BENCH, root / 'bench',
                    ignore=shutil.ignore_patterns('tests', '__pycache__'))
    bench = json.loads((harness.ROOT / 'BENCHMARK.json').read_text())
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / 'bench').rglob('*') if p.is_file()}

    cfg = harness.load_json(harness.BENCH / 'configs'
                            / 'qwen3-0.6b_on.internlm2-1.8b_off.json')
    cfg['name'] = 'tiny'
    (root / 'bench/configs/tiny.json').write_text(json.dumps(cfg))
    (root / 'bench/traffic/every.py').write_text(textwrap.dedent('''
        """One arrival every ``gap_s`` seconds."""
        def periods(p, duration, rng):
            n = int(duration / p['gap_s'])
            return [(p['gap_s'], 1, 'all') for _ in range(n)]

        def spread(p, length, n, rng):
            return [0.0] * n
    '''))
    (root / 'bench/traffic/steady.json').write_text(json.dumps({
        'online': [{'arrival': {'kind': 'every', 'gap_s': 2.5},
                    'prompt': {'law': 'uniform', 'low': 8, 'high': 8},
                    'output': {'law': 'uniform', 'low': 4, 'high': 4}}]}))
    (root / 'bench/metrics/engine.steps_seen.py').write_text(
        'def read(run):\n    return len(run.steps) or None\n')
    bench['configs'].append({'name': 'tiny', 'source': 'x',
                             'file': 'bench/configs/tiny.json',
                             'reduced': [], 'why': 'x'})
    bench['workloads'].append({'name': 'tiny.steady', 'config': 'tiny',
                               'traffic': 'steady', 'chips': 1, 'why': 'x'})
    bench['per_layer'].append({'name': 'engine.steps_seen', 'unit': 'steps',
                               'better': 'higher', 'source': 'program_span',
                               'layer': 'engine', 'moves': 'ttft_p90_ms'})
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))

    out = subprocess.run([sys.executable, '-c', PROBE, str(root / 'bench')],
                         capture_output=True, text=True, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got['chips'] == 1 and got['online_model'] == 'qwen3-0.6b'
    assert got['arrivals'] == [0.0, 2.5, 5.0, 7.5]
    assert 'ttft_p90_ms' in got['end_to_end']
    assert 'engine.steps_seen' in got['per_layer']
    assert got['new_metric'] is None          # nothing to read: left out
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / 'bench').rglob('*') if p.is_file()}
    assert all(after[k] == v for k, v in before.items())


def test_arrivals_are_the_same_set_for_every_seed():
    """The seed orders a fixed set of arrivals and sizes: it changes when
    and in which order, not how much work a run offers."""
    import workload
    mix = workload.load_mix('chat_burst')
    a = workload.online_schedule(mix, 51.0, 1, vocab=1000, part=1)
    b = workload.online_schedule(mix, 51.0, 2 ** 31 + 99, vocab=1000, part=1)
    assert len(a.arrivals) == len(b.arrivals) > 0
    assert sorted(len(x.prompt) for x in a.arrivals) == \
        sorted(len(x.prompt) for x in b.arrivals)
    assert sorted(x.max_tokens for x in a.arrivals) == \
        sorted(x.max_tokens for x in b.arrivals)
    assert [x.t for x in a.arrivals] != [x.t for x in b.arrivals]


ARCH_PROBE = textwrap.dedent('''
    import json, sys
    sys.path.insert(0, sys.argv[1])
    sys.path.insert(0, sys.argv[2])
    import flops, harness, reference
    cell = harness.load_cell('toy.steady')
    online = cell.config['online']
    hf = online['config']
    small = dict(hf, hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 num_hidden_layers=2, vocab_size=512)
    ref = reference.Reference(small, 3)
    gap, _ = ref.gaps([5, 6, 7], [8, 9])
    print(json.dumps({
        'arch': hf['architectures'][0],
        'd_ff': harness.model_config(online, 16).d_ff,
        'ref_mark': ref.mark, 'gaps': len(gap),
        'body_params': flops.body_params(hf),
        'decode_calls': flops.paged_decode_calls(hf, [10, 2]),
        'decode_step_flops': flops.decode_step_flops(hf, [10]),
        'mixed_step_flops': flops.mixed_step_flops(hf, [(0, 6)], []),
        'offline_body_params': flops.body_params(
            cell.config['offline'][0]['config'])}))
''')

TOY_ARCH = textwrap.dedent('''
    """A toy architecture: the shared dense decoder with marks, whose
    layers attend to a window of 4 keys."""
    from __future__ import annotations

    from dataclasses import dataclass

    from archs import _decoder
    from archs._decoder import unembed_flops  # noqa: F401


    @dataclass(frozen=True)
    class Marks:
        width: int


    MARK = Marks(4242).width
    WINDOW = 4


    def program_config(hf, page_size):
        return dict(_decoder.program_config(hf, page_size, qk_norm=True),
                    d_ff=MARK)


    class Reference(_decoder.Reference):
        mark = MARK

        def __init__(self, hf, seed, mesh=None):
            super().__init__(hf, seed, mesh, qk_norm=True)


    def body_params(hf):
        return MARK


    def attn_flops(hf, prefill, decode_live):
        return MARK * _decoder.attended_keys(prefill, decode_live, WINDOW)


    def paged_decode_calls(hf, live):
        s = _decoder.spec_of(hf)
        return [_decoder.paged_call(s, [min(n, WINDOW) for n in live])]
''')


def test_new_architecture_arrives_as_one_file(tmp_path):
    """A configuration whose models name a new architecture brings
    ``bench/archs/<name>.py``: its program config, reference and counts
    are found by that name, with no existing file edited."""
    root = tmp_path / 'checkout'
    shutil.copytree(harness.BENCH, root / 'bench',
                    ignore=shutil.ignore_patterns('tests', '__pycache__'))
    bench = json.loads((harness.ROOT / 'BENCHMARK.json').read_text())
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / 'bench').rglob('*') if p.is_file()}

    (root / 'bench/archs/ToyForCausalLM.py').write_text(TOY_ARCH)
    cfg = harness.load_json(harness.BENCH / 'configs'
                            / 'qwen3-0.6b_on.internlm2-1.8b_off.json')
    cfg['name'] = 'toy'
    cfg['online']['model'] = 'toy-0.6b'
    cfg['online']['config']['architectures'] = ['ToyForCausalLM']
    (root / 'bench/configs/toy.json').write_text(json.dumps(cfg))
    bench['configs'].append({'name': 'toy', 'source': 'x',
                             'file': 'bench/configs/toy.json',
                             'reduced': [], 'why': 'x'})
    bench['workloads'].append({'name': 'toy.steady', 'config': 'toy',
                               'traffic': 'chat_alone', 'chips': 1,
                               'why': 'x'})
    (root / 'BENCHMARK.json').write_text(json.dumps(bench))

    out = subprocess.run([sys.executable, '-c', ARCH_PROBE,
                          str(root / 'bench'), str(harness.ROOT / 'src')],
                         capture_output=True, text=True, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got['arch'] == 'ToyForCausalLM'
    assert got['d_ff'] == got['ref_mark'] == got['body_params'] == 4242
    assert got['gaps'] == 2
    # one call a step, over at most the window's keys: qwen3's 16 query
    # and 8 KV heads of 128, rows reading 4 and 2 tokens
    assert got['decode_calls'] == [[4 * 16 * 128 * 6,
                                    2 * 2 * 16 * 128 * 2
                                    + 2 * 6 * 8 * 128 * 2]]
    assert got['decode_step_flops'] == (2 * 4242 + 2 * 1024 * 151936
                                        + 4242 * 4)
    # six prefill tokens from 0 see 1, 2, 3, 4, 4, 4 keys
    assert got['mixed_step_flops'] == (2 * 4242 * 6 + 2 * 1024 * 151936
                                       + 4242 * 18)
    # the offline model still reads its own architecture's counts
    assert got['offline_body_params'] == 24 * 62_914_560
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / 'bench').rglob('*') if p.is_file()}
    assert all(after[k] == v for k, v in before.items())
