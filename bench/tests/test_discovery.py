"""A later cell is data: a new configuration, traffic mix, arrival kind and
per-layer metric become visible by their files and one new entry in
BENCHMARK.json, with no existing file edited."""
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
