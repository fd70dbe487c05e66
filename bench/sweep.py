"""Knee sweep: the highest online rate a configuration sustains alone.

    python3 bench/sweep.py --config qwen3-0.6b_on.internlm2-1.8b_off \\
        --mix chat_alone --rates 1 1.5 2 2.5 --seeds 11 12 13 --seconds 51

Serves the configuration's node with no offline work, under Poisson
arrivals with the lengths of the named mix's first online stream, at each
rate in turn (ascending) and, at each rate, once per seed.  Each
measurement starts from an idle node: the one before it has drained
completely (every stream finished, nothing queued or running), so no
backlog carries from one measurement into the next.  A configuration
that declares a mesh is served over it, as a cell's run serves it.

For each measurement it prints what was offered and completed per second
in the window, the median time to first token of the window's first and
last thirds, and the slope of the online queue (requests submitted but
not yet admitted) across the window.  A measurement is *flat* where the
queue does not grow (slope at most ``FLAT_SLOPE`` per second, under one
request over a 51-s window), the last third's median TTFT is at most
``FLAT_TTFT`` times the first third's, and nothing failed.  The knee is
the highest rate flat on every seed, with every lower rate flat on every
seed too; it is written into the traffic files by hand, as a number.
With ``--stop`` the sweep ends after the first rate that is not flat on
every seed.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import asyncio      # noqa: E402
import copy         # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
from pathlib import Path    # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
FLAT_SLOPE = 0.02   # queued requests per second
FLAT_TTFT = 1.5     # last third's median TTFT over the first third's
DRAIN_S = 240.0     # how long a measurement may take to drain


def measure(node, harness, mix, rate, seconds, seed):
    import e2e
    if node.has_work():
        raise RuntimeError('sweep: node not idle before a measurement')
    mix = copy.deepcopy(mix)
    stream = mix['online'][0]
    stream['arrival'] = {'kind': 'poisson', 'rps': rate}
    mix['online'] = [stream]
    mix.pop('offline', None)
    run = harness.Run(None, float(seconds))
    clock = harness.Clock(node.clock)
    queue = []

    async def sample():
        while not run.w1 or clock.now() < run.w1:
            if run.w0 and clock.now() >= run.w0:
                queue.append((clock.now() - run.w0, len(node.online.queue)))
            await clock.until(clock.now() + 0.25)

    async def both():
        await asyncio.gather(
            harness.drive(node, run, mix, seed, clock, trace=False,
                          drain_s=DRAIN_S), sample())
    t0 = clock.now()
    asyncio.run(both())
    win = [r for r in run.online if r.in_window]
    done = [r for r in run.online
            if r.ok and run.w0 <= r.t_last < run.w1]
    third = seconds / 3
    first = [r.ttft for r in win if r.due < run.w0 + third]
    last = [r.ttft for r in win if r.due >= run.w1 - third]
    t, q = np.asarray(queue).T if queue else (np.zeros(2), np.zeros(2))
    slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else float('nan')
    om = e2e.online_metrics(run.online)
    p50_first = 1e3 * e2e.percentile(first, 50)
    p50_last = 1e3 * e2e.percentile(last, 50)
    flat = (slope <= FLAT_SLOPE and p50_last <= FLAT_TTFT * p50_first
            and om['failed'] == 0)
    return {'rate': rate, 'seed': seed, 'offered_per_s': len(win) / seconds,
            'completed_per_s': len(done) / seconds,
            'failed': om['failed'],
            'ttft_p50_first_third_ms': p50_first,
            'ttft_p50_last_third_ms': p50_last,
            'ttft_p90_ms': om['ttft_p90_ms'],
            'tpot_p90_ms': om['tpot_p90_ms'],
            'queue_mean': float(np.mean(q)),
            'queue_slope_per_s': slope,
            'drain_s': clock.now() - run.w1,
            'idle_after': not node.has_work(),
            'wall_s': clock.now() - t0,
            'flat': bool(flat)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--config', required=True)
    ap.add_argument('--mix', required=True)
    ap.add_argument('--rates', type=float, nargs='+', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', default=[1])
    ap.add_argument('--seconds', type=float, default=51.0)
    ap.add_argument('--stop', action='store_true',
                    help='end after the first rate not flat on every seed')
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / 'src'))
    import harness
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_node
    if jax.devices()[0].platform != 'tpu':
        print('sweep: needs a TPU', file=sys.stderr)
        return 2
    enable_compile_cache()
    cfg = harness.load_json(ROOT / 'bench' / 'configs'
                            / f'{args.config}.json')
    mix = harness.workload.load_mix(args.mix)
    page = cfg['page_size']
    model_seed = args.seeds[0] % (2 ** 31 - 1024)
    node = build_node(harness.model_config(cfg['online'], page),
                      [harness.model_config(o, page)
                       for o in cfg['offline']],
                      seed=model_seed, mesh=harness.node_mesh(cfg),
                      **cfg['node'])
    harness.warm_up(node, model_seed)
    print(f'sweep {args.config} / {args.mix}: set-up '
          f'{time.monotonic() - T_START:.1f} s', flush=True)
    rows, verdicts = [], {}
    for rate in sorted(args.rates):
        flat = True
        for seed in args.seeds:
            row = measure(node, harness, mix, rate, args.seconds, seed)
            rows.append(row)
            flat &= row['flat'] and row['idle_after']
            print(json.dumps(row), flush=True)
        verdicts[rate] = flat
        if args.stop and not flat:
            break
    knee = None
    for rate in sorted(verdicts):
        if not verdicts[rate]:
            break
        knee = rate
    print(json.dumps({'config': args.config, 'mix': args.mix,
                      'seconds': args.seconds, 'seeds': args.seeds,
                      'flat': {str(r): v for r, v in verdicts.items()},
                      'knee_rps': knee, 'rows': rows}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
