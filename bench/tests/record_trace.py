"""Record the small profiler trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_trace.py      # on one TPU chip

Serves a few qwen3-0.6b requests (published widths, online engine alone)
through ``NodeOrchestrator.step`` under the benchmark's own spans, traces
a stretch with mixed and pure-decode steps, and writes
``bench/tests/data/decode_trace.xplane.pb`` with, beside it,
``decode_trace.json``: the steps the host recorded in that stretch.  Also
prints the trace's planes and lines and its most frequent op names.
"""
from __future__ import annotations

import collections
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / 'src'))

import harness      # noqa: E402

OUT = HERE / 'data'


def main() -> int:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_node

    if jax.devices()[0].platform != 'tpu':
        print('record_trace: needs a TPU', file=sys.stderr)
        return 2
    enable_compile_cache()
    cfg = harness.load_json(BENCH / 'configs'
                            / 'qwen3-0.6b_on.internlm2-1.8b_off.json')
    node = build_node(harness.model_config(cfg['online'], 16), [],
                      n_handles=8, pages_per_handle=16, max_seq=512)
    eng = node.online
    harness.warm_up(node, 0)
    run = harness.Run(None, 0.0)
    clock = harness.Clock(node.clock)
    harness.instrument(run, node, clock, annotate=True)
    for i in range(4):
        eng.submit(list(range(1 + i, 201 + 17 * i)), max_new_tokens=6)
    tdir = BENCH.parent / '.bench_out' / 'record_trace'
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    with jax.profiler.TraceAnnotation('bench.window'):
        pass
    t0 = clock.now()
    node.drain()
    t1 = clock.now()
    with jax.profiler.TraceAnnotation('bench.window'):
        pass
    jax.profiler.stop_trace()
    OUT.mkdir(exist_ok=True)
    src = harness._devtrace().find_xplane(str(tdir))
    shutil.copy(src, OUT / 'decode_trace.xplane.pb')
    steps = [{'kind': s.kind, 'prefill': s.prefill, 'live': s.live,
              'ms': 1e3 * (s.t1 - s.t0)} for s in run.steps_in(t0, t1)]
    (OUT / 'decode_trace.json').write_text(json.dumps(
        {'model': cfg['online']['config'], 'steps': steps}, indent=1))

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(OUT / 'decode_trace.xplane.pb'))
    for plane in pd.planes:
        for line in plane.lines:
            names = collections.Counter(ev.name for ev in line.events)
            print(f'{plane.name} | {line.name} | {sum(names.values())} '
                  f'events | {names.most_common(12)}')
    print(json.dumps(steps))
    return 0


if __name__ == '__main__':
    sys.exit(main())
