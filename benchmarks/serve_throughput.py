"""Serving-plane throughput — not a paper figure; this benchmark tracks the
repo's own serving trajectory (ROADMAP: every PR makes a hot path measurably
faster or records why not).

Three experiments, one JSON:

1. **batched chunked prefill vs the seed path** — a fixed offline workload
   drained to completion under (a) the seed one-request-at-a-time prefill
   (``max_prefill_reqs=1``, no decode piggyback) and (b) the batch-composition
   scheduler (multi-request budgeted prefill + piggybacked decode).  Greedy
   outputs must be identical; scheduler steps-to-completion must drop.
2. **node demo** — the heterogeneous NodeOrchestrator demo under bursty
   online traffic: online TTFT/TPOT p50, offline tokens/s, dispatches/s.
3. **streaming front-end** — the async HTTP surface under trace-replayed
   load: ≥ 64 concurrent SSE streams (front-loaded arrival burst) with an
   offline batch job backfilling, through the in-process ASGI client (the
   exact server code path minus the socket).  Records requests/s, p50/p99
   TTFT and peak concurrency; hard gates: every stream completes, peak
   concurrency ≥ 64, and the ≤ 1-preemption-per-online-request bound holds.

Writes ``results/serve_throughput.json`` (benchmark convention) and mirrors
it to ``BENCH_serve.json`` at the repo root (the perf-trajectory record).
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict

import numpy as np


def _drain_offline(batched: bool, *, n_reqs: int = 8, prompt: int = 24,
                   gen: int = 16, seed: int = 0) -> Dict:
    """Steps-to-completion for a fixed offline backlog under one scheduler
    configuration (no runtime — pure serving-plane measurement)."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models.api import build_model
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.kvpool import KVPool

    cfg = reduced(get_config('qwen3-0.6b'), page_size=4)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    pool = KVPool(n_handles=24, pages_per_handle=8, page_size=4,
                  reserved_handles=1)
    ecfg = EngineConfig(
        max_batch=8, max_seq=64, prefill_chunk=16,
        max_prefill_reqs=4 if batched else 1,
        piggyback_decode=batched, klass='offline')
    eng = Engine(model, params, pool, ecfg)
    rng = np.random.default_rng(seed)
    rids = [eng.submit(rng.integers(1, cfg.vocab_size, prompt).tolist(),
                       max_new_tokens=gen) for _ in range(n_reqs)]
    t0 = time.monotonic()
    eng.run_to_completion()
    wall = time.monotonic() - t0
    return {
        'steps': eng.stats.steps,
        'dispatches': eng.stats.dispatches,
        'mixed_dispatches': eng.stats.mixed_dispatches,
        'prefill_chunks': eng.stats.prefill_chunks,
        'decode_iterations': eng.stats.decode_iterations,
        'tokens': eng.stats.tokens_generated,
        'wall_s': wall,
        'outputs': [eng.output_tokens(r) for r in rids],
    }


def _streaming_frontend(n_streams: int = 72, max_new: int = 6,
                        horizon_s: float = 2.0, seed: int = 0) -> Dict:
    """Trace-replay the async front-end: every arrival opens a live SSE
    stream through the ASGI app while one batch job backfills offline."""
    import asyncio

    from repro.core.clock import RealClock
    from repro.launch.serve import demo_node
    from repro.serving.frontend.app import FrontendApp
    from repro.serving.frontend.driver import AsyncNodeDriver
    from repro.serving.frontend.loadgen import (
        LoadGenerator, TraceEntry, make_online_trace)
    from repro.serving.frontend.testing import ASGIClient

    node = demo_node(clock=RealClock())
    # all arrivals in the first 10% of the horizon → peak concurrency is
    # the whole trace (streams outlive the arrival window)
    trace = make_online_trace(n_streams, horizon_s=horizon_s,
                              prompt_len=12, max_new_tokens=max_new,
                              seed=seed, burst_frac=1.0)
    trace.append(TraceEntry(t=0.0, kind='batch', n_requests=6,
                            prompt_len=16, max_new_tokens=12,
                            seed=seed + 500))

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            client = ASGIClient(FrontendApp(driver))
            gen = LoadGenerator(client, node.clock,
                                vocab_size=node.online.mcfg.vocab_size)
            report = await gen.replay(trace)
            # streams are done; let the pump drain the offline batch
            while node.has_work():
                await asyncio.sleep(1e-3)
            return report

    t0 = time.monotonic()
    report = asyncio.run(scenario())
    wall = time.monotonic() - t0
    node.runtime.check_invariants()
    m = node.metrics()

    if report.completed != n_streams:
        raise RuntimeError(f'streaming front-end dropped requests: '
                           f'{report.completed}/{n_streams} completed')
    if report.peak_concurrent_streams < 64:
        raise RuntimeError(f'peak concurrency {report.peak_concurrent_streams}'
                           f' < 64 — the burst did not overlap')
    if m['max_preemptions_per_request'] > 1:
        raise RuntimeError('preemption bound violated under streaming load')

    out = report.to_dict()
    out.update({
        'wall_s': wall,
        'offline_tokens': m['offline_tokens'],
        'compute_preemptions': m['compute_preemptions'],
        'max_preemptions_per_request': m['max_preemptions_per_request'],
        'cancellations': m['cancellations'],
    })
    return out


def run(steps: int = 200, out_path: str = 'results/serve_throughput.json',
        bench_path: str = 'BENCH_serve.json') -> Dict:
    from repro.launch.serve import serve_demo

    single = _drain_offline(batched=False)
    batched = _drain_offline(batched=True)
    # explicit raises (not assert): these gates must hold even under -O —
    # BENCH_serve.json is the perf-trajectory record the README cites
    if batched['outputs'] != single['outputs']:
        raise RuntimeError('batched scheduler changed greedy outputs')
    for r in (single, batched):
        r.pop('outputs')
    if batched['steps'] >= single['steps']:
        raise RuntimeError(
            f"batched prefill did not reduce steps-to-completion: "
            f"{batched['steps']} vs {single['steps']}")

    t0 = time.monotonic()
    demo = serve_demo(steps=steps, quiet=True)
    demo_wall = time.monotonic() - t0
    total_dispatches = (demo['online_dispatches']
                       + demo['offline_dispatches'])

    streaming = _streaming_frontend()

    result = {
        'prefill_composition': {
            'seed_single_request': single,
            'batched_scheduler': batched,
            'steps_delta': single['steps'] - batched['steps'],
            'steps_reduction_pct': round(
                100.0 * (single['steps'] - batched['steps'])
                / single['steps'], 1),
        },
        'node_demo': {
            'steps': steps,
            'wall_s': demo_wall,
            'online_ttft_p50_s': demo['online_ttft_p50'],
            'online_tpot_p50_s': demo['online_tpot_p50'],
            'offline_tokens': demo['offline_tokens'],
            'offline_tokens_per_s': demo['offline_tokens'] / demo_wall,
            'dispatches_per_s': total_dispatches / demo_wall,
            'compute_preemptions': demo['compute_preemptions'],
            'max_preemptions_per_request':
                demo['max_preemptions_per_request'],
            'engines': demo['engines'],
        },
        'streaming_frontend': streaming,
    }
    os.makedirs(os.path.dirname(out_path) or '.', exist_ok=True)
    for path in (out_path, bench_path):
        with open(path, 'w') as f:
            json.dump(result, f, indent=1)
    pc = result['prefill_composition']
    nd = result['node_demo']
    print(f"batched prefill: {batched['steps']} steps vs seed "
          f"{single['steps']} (-{pc['steps_reduction_pct']}%), "
          f"outputs identical")
    print(f"node demo: ttft_p50={nd['online_ttft_p50_s']}s "
          f"tpot_p50={nd['online_tpot_p50_s']}s "
          f"offline={nd['offline_tokens_per_s']:.1f} tok/s "
          f"dispatches={nd['dispatches_per_s']:.1f}/s")
    sf = result['streaming_frontend']
    print(f"streaming front-end: {sf['completed']} streams "
          f"(peak {sf['peak_concurrent_streams']} concurrent) "
          f"{sf['requests_per_s']:.1f} req/s "
          f"ttft_p50={sf['ttft_p50_s']:.3f}s "
          f"ttft_p99={sf['ttft_p99_s']:.3f}s "
          f"max_preempt/req={sf['max_preemptions_per_request']}")
    return result


if __name__ == '__main__':
    run()
