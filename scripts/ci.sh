#!/usr/bin/env bash
# CI gate: tier-1 suite + a fast kernel-parity subset.
#
# The kernel-parity subset re-runs first and verbosely even though tier-1
# includes it: the Pallas kernels are where jax API drift lands, so a jax
# bump that breaks them fails loudly at the top of the log instead of
# somewhere inside the full run.
#
# Usage:  scripts/ci.sh [--kernels-only|--regen-api]
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [[ "${1:-}" == "--regen-api" ]]; then
    # deliberate public-API change: refresh the pinned snapshot
    python -m repro.core.api > tests/api_surface.txt
    echo "regenerated tests/api_surface.txt ($(wc -l < tests/api_surface.txt) lines)"
    exit 0
fi

echo "== jax version: $(python -c 'import jax; print(jax.__version__)')"

echo "== valve patch surface =="
# single source of truth for the counts lives in tests/test_patch_surface.py
python - <<'PY'
import sys
sys.path.insert(0, 'tests')
from test_patch_surface import patch_loc, session_patch_loc
loc, sloc = patch_loc(), session_patch_loc()
print(f'framework-side patch: {loc} LOC (paper Table 1 contract: < 20; '
      f'memory-plane v1 budget: <= 13)')
print(f'session-API integration: {sloc} tagged lines (open/mint/admit/'
      f'finish/gate/notify)')
assert 0 < loc <= 13, loc   # surviving-prefix resume must not bloat it
assert 0 < sloc < 10, sloc
PY

echo "== memory-plane lease property smoke (fast gate) =="
python -m pytest -q tests/test_memory.py

echo "== control-plane API surface (pinned snapshot) =="
python - <<'PY'
from repro.core.api import api_surface
want = open('tests/api_surface.txt').read().splitlines()
got = api_surface()
assert got == want, ('public API drifted from tests/api_surface.txt — '
                     'if intentional, run scripts/ci.sh --regen-api')
print(f'API surface matches snapshot ({len(got)} lines)')
PY

echo "== node demo smoke (heterogeneous colocation) =="
python -m repro.launch.serve --steps 50

echo "== serving front-end: SSE conformance (fast gate) =="
python -m pytest -q tests/test_sse.py

echo "== serving front-end: in-process HTTP smoke (1 stream + 1 batch, no sockets) =="
python - <<'PY'
import asyncio, json
from repro.configs import get_config, reduced
from repro.core.clock import VirtualClock
from repro.core.runtime import RuntimeConfig, ValveRuntime
from repro.launch.node import NodeOrchestrator
from repro.serving.engine import EngineConfig
from repro.serving.frontend.app import FrontendApp
from repro.serving.frontend.driver import AsyncNodeDriver, clock_sleep
from repro.serving.frontend.testing import ASGIClient
from repro.serving.kvpool import KVPool

pool = KVPool(6, 4, page_size=4, reserved_handles=1)
rt = ValveRuntime(pool, RuntimeConfig(n_devices=1, t_cool_init=0.002),
                  clock=VirtualClock())
node = NodeOrchestrator(rt, idle_advance=1e-3)
for klass, seed in (('online', 0), ('offline', 1)):
    node.add_engine(reduced(get_config('qwen3-0.6b'), page_size=4),
                    EngineConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                                 klass=klass), seed=seed)

async def main():
    async with AsyncNodeDriver(node) as driver:
        client = ASGIClient(FrontendApp(driver))
        sr = client.stream('POST', '/v1/completions',
                           json={'prompt': [5, 7, 11], 'max_tokens': 4,
                                 'stream': True})
        toks = 0
        async with sr:
            assert sr.status == 200, sr.status
            async for ev in sr.events():
                if ev.done:
                    break
                if json.loads(ev.data)['choices'][0].get('token') is not None:
                    toks += 1
        assert toks == 4, toks
        job = (await client.post('/v1/batches', json={
            'requests': [{'prompt': [3, 1, 4], 'max_tokens': 3}]})).json()
        for _ in range(20000):
            st = (await client.get(f"/v1/batches/{job['id']}")).json()['status']
            if st == 'completed':
                break
            await clock_sleep(node.clock, 1e-4)
        assert st == 'completed', st
        res = (await client.get(f"/v1/batches/{job['id']}/results")).json()
        assert len(res['results'][0]['tokens']) == 3, res

asyncio.run(main())
node.runtime.check_invariants()
assert node.runtime.invalidation_routes() == []
print('front-end smoke OK: 1 SSE stream (4 tokens) + 1 batch job, in-process')
PY

echo "== rate-estimator warm-up regressions (fast gate) =="
python -m pytest -q tests/test_rate_estimators.py

echo "== cluster-harness smoke (small fleet, short horizon) =="
python - <<'PY'
from repro.core.cluster.harness import HarnessConfig, make_harness
from repro.core.sim.colocation import SimConfig

cfg = HarnessConfig(n_nodes=3, gpus_per_node=2, epoch_s=20.0, n_epochs=2,
                    sim=SimConfig(total_pages=1024), measure_baseline=False)
h = make_harness(cfg)
h.run()
assert h.scheduler.placements, 'smoke fleet placed no offline jobs'
assert all(g.source == 'nodesim'
           for t in h.scheduler.nodes.values() for g in t.gpus), \
    'scheduler consumed non-measured telemetry'
print(f'cluster smoke OK: {len(h.scheduler.placements)} jobs placed, '
      f'util {h.reports[-1].utilization_gain_measured:.1%}')
PY

echo "== kernel parity (fast subset, interpret mode) =="
python -m pytest -q \
    tests/test_kernels_flash.py \
    tests/test_kernels_paged.py \
    tests/test_kernels_sampling.py \
    tests/test_kernels_rwkv6.py \
    tests/test_kernel_integration.py

if [[ "${1:-}" == "--kernels-only" ]]; then
    exit 0
fi

echo "== kernel hot-path smoke (fused decode regression gate) =="
python benchmarks/kernel_hotpath.py --smoke

echo "== shard-scale smoke (mesh parity + zero-recompute rescue gate) =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/shard_scale.py --smoke

echo "== disagg smoke (2-pool handoff: bit-identity + zero-recompute gate) =="
python benchmarks/disagg.py --smoke

echo "== fleet-placement smoke (global ≥ greedy + vectorized-sim gate) =="
python benchmarks/fleet_placement.py --smoke

echo "== tier-1 =="
python -m pytest -x -q

echo "CI green."
