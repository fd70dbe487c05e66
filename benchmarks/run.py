"""Benchmark orchestrator — one experiment per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Writes per-benchmark JSON to results/ and prints each table.  The dry-run
sweep itself (results/dryrun.jsonl) is produced by
``python -m repro.launch.dryrun --sweep``; benchmarks.roofline consumes it.
See benchmarks/README.md for the script ↔ paper-figure map.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

os.makedirs('results', exist_ok=True)

BENCHES = [
    ('preemption_latency', 'paper §4.1 — serial vs fan-out gate latency'),
    ('decode_gaps', 'paper Fig. 4 — decode-gap telemetry + T_cool'),
    ('miad_convergence', 'paper §5 — MIAD reclamation-rate convergence'),
    ('eviction_policy', 'paper Fig. 11 — Algorithm 1 vs FIFO'),
    ('colocation_matrix', 'paper Fig. 10 — 10 pairs × 6 strategies'),
    ('cluster_utilization', 'paper Fig. 8/9 — fleet utilization + savings'),
    ('cluster_harvest', 'paper §6–7 — closed-loop NodeSim-telemetry fleet'),
    ('roofline', 'supporting analysis — dry-run roofline table'),
    ('serve_throughput', 'serving plane — batched prefill vs seed + node demo'),
    ('api_overhead', 'control-plane API v1 — session/event hot-path cost'),
    ('prefix_reuse', 'memory plane v1 — prefix sharing + partial-invalidation tax'),
    ('kernel_hotpath', 'kernel hot path — fused sampling + prefix-shared decode step'),
    ('shard_scale', 'multi-device plane — mesh scaling + cross-pool rescue tax'),
    ('disagg', 'disaggregated plane — prefill/decode split vs colocated, '
               'zero-recompute handoff'),
    ('fleet_placement', 'placement plane — global optimizer vs greedy on a '
                        'heterogeneous 100-node fleet + vectorized-sim gate'),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--only', default=None)
    ap.add_argument('--fast', action='store_true',
                    help='shorter horizons for CI')
    args = ap.parse_args()
    # every benchmark here is a CPU run (benchmarks/README.md); shard_scale
    # needs virtual devices, which must exist before jax initializes
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +
                               ' --xla_force_host_platform_device_count=8')

    failures = []
    for name, desc in BENCHES:
        if args.only and name != args.only:
            continue
        print(f'\n=== {name}: {desc} ===', flush=True)
        t0 = time.time()
        try:
            mod = __import__(f'benchmarks.{name}', fromlist=['run'])
            if args.fast and name == 'colocation_matrix':
                mod.run(n_pairs=4, horizon_s=150.0)
            elif args.fast and name == 'eviction_policy':
                mod.run(horizon_s=150.0)
            elif args.fast and name == 'miad_convergence':
                mod.run(horizon_s=150.0)
            elif args.fast and name == 'serve_throughput':
                mod.run(steps=100)
            elif args.fast and name == 'cluster_harvest':
                mod.run(n_nodes=8, epoch_s=30.0, n_epochs=4)
            elif args.fast and name == 'api_overhead':
                mod.run(horizon_s=60.0)
            elif args.fast and name == 'prefix_reuse':
                mod.run(horizon_s=120.0)
            elif args.fast and name == 'kernel_hotpath':
                mod.run(warm=12, steps=24, gen=64)
            elif args.fast and name == 'shard_scale':
                mod.run(mesh_sizes=(1, 2, 4), warm=12, steps=16, gen=64)
            elif args.fast and name == 'disagg':
                mod.run(n_online=4, gap=6, n_offline=2)
            elif args.fast and name == 'fleet_placement':
                mod.run_smoke()
            else:
                mod.run()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f'--- {name} finished in {time.time() - t0:.1f}s', flush=True)

    if failures:
        print(f'\nFAILED benchmarks: {failures}')
        sys.exit(1)
    print('\nall benchmarks complete; JSON in results/')


if __name__ == '__main__':
    main()
