"""Multi-device serving plane: mesh scaling + cross-pool rescue economics.

Two trajectories in one file (``BENCH_shard.json``):

1. **Mesh scaling** — the same decode-heavy drain at tensor-parallel mesh
   sizes 1 (``mesh=None``, the untouched single-device path), 2, 4, 8 over
   *virtual* CPU devices (``--xla_force_host_platform_device_count``, the
   ``launch/dryrun.py`` trick).  On virtual devices the numbers measure
   GSPMD partitioning OVERHEAD, not speedup — CPU "devices" share one
   socket, so tokens/s goes *down* with mesh size.  What the trajectory
   pins is (a) the overhead factor staying sane and (b) greedy outputs
   staying bit-identical wherever the partitioning is exact: every mesh
   width that divides ``n_kv_heads`` must not change a single token
   (hard gate).  Wider meshes overshard the kv-head axis — GSPMD
   replicates it and reorders the contraction, and under bf16 a
   near-tied argmax can flip (the same drain in float32 IS bit-identical
   at every width) — so those sizes record ``tokens_until_divergence``
   in the trajectory instead of hard-failing.

2. **Burst recompute tax** — the node-level online burst from
   ``tests/test_node_migration.py`` with cross-pool rescue ON (an
   auxiliary pool registered) vs OFF (PR-5 truncate-and-recompute).
   Hard gates, enforced here and in CI (``--smoke``):

   - rescue ON reclaims with **zero** offline recomputed tokens;
   - recompute(ON) ≤ recompute(OFF) — migration must never cost more
     compute than the truncation it replaces;
   - at least one victim is actually rescued (≥1 cross-pool migration).

The caller provides the virtual devices; the module sets no environment:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python benchmarks/shard_scale.py --smoke

(``scripts/ci.sh`` and ``benchmarks/run.py`` do this.)  Mesh sizes the
process has no devices for are skipped.

Writes ``results/shard_scale.json`` and mirrors ``BENCH_shard.json`` at
the repo root.  ``--smoke`` runs mesh sizes {1, 2} with a short window
plus the full (cheap) rescue comparison.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

ARCH = 'qwen3-0.6b'


def _mesh(n: Optional[int]):
    import jax
    from jax.sharding import Mesh
    if n is None or n == 1:
        return None
    devs = jax.devices()
    if len(devs) < n:
        return None                      # flag ineffective — skip this size
    return Mesh(np.asarray(devs[:n]), ('model',))


def _measure_mesh(n_dev: int, *, warm: int, steps: int, gen: int) -> Optional[Dict]:
    """Steady-state decode µs/step at tensor-parallel width ``n_dev``."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models.api import build_model
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.kvpool import KVPool

    mesh = _mesh(n_dev)
    if n_dev > 1 and mesh is None:
        return None
    cfg = reduced(get_config(ARCH), page_size=4)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    pool = KVPool(40, 4, page_size=4, reserved_handles=1)
    eng = Engine(model, params, pool,
                 EngineConfig(max_batch=4, max_seq=160, prefill_chunk=16,
                              mesh=mesh))
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(1, cfg.vocab_size, 24).tolist(),
                       max_new_tokens=gen) for _ in range(4)]
    while (eng.queue
           or any(not eng.requests[r].generated for r in rids)
           or eng.stats.decode_iterations < warm):
        if not eng.step():
            break
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    eng.flush_tokens()
    wall = time.perf_counter() - t0
    eng.run_to_completion()
    return {
        'mesh_devices': n_dev,
        'us_per_decode_step': wall / steps * 1e6,
        'decode_tokens_per_s': eng.cfg.max_batch / wall * steps,
        '_outputs': [eng.output_tokens(r) for r in rids],
    }


def _burst_node(rescue: bool):
    """The tests/test_node_migration.py scenario, benchmark-sized."""
    from repro.configs import get_config, reduced
    from repro.core.clock import VirtualClock
    from repro.core.runtime import RuntimeConfig, ValveRuntime
    from repro.launch.node import NodeOrchestrator
    from repro.serving.engine import EngineConfig
    from repro.serving.kvpool import KVPool

    def ecfg(klass):
        return EngineConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                            klass=klass)

    pool = KVPool(5, 4, page_size=4, reserved_handles=1, name='poolA')
    rt = ValveRuntime(pool, RuntimeConfig(n_devices=1, t_cool_init=0.002),
                      clock=VirtualClock())
    node = NodeOrchestrator(rt, idle_advance=1e-3)
    cfg = reduced(get_config(ARCH), page_size=4)
    node.add_engine(cfg, ecfg('online'), seed=0, name='online')
    node.add_engine(cfg, ecfg('offline'), seed=0, name='offA')
    if rescue:
        pool_b = node.add_pool(KVPool(8, 4, page_size=4, name='poolB'))
        node.add_engine(cfg, ecfg('offline'), seed=0, name='offB',
                        pool=pool_b)
    return node


def _measure_rescue(rescue: bool) -> Dict:
    node = _burst_node(rescue)
    rng = np.random.default_rng(7)
    eng = node.names['offA']
    for _ in range(2):
        eng.submit(rng.integers(1, eng.mcfg.vocab_size, 12).tolist(),
                   max_new_tokens=8)
    for _ in range(4):
        node.step()
    node.online.submit(
        rng.integers(1, node.online.mcfg.vocab_size, 28).tolist(),
        max_new_tokens=12)
    node.drain(max_steps=5000)
    node.runtime.check_invariants()
    offline_recompute = sum(e.stats.tokens_recomputed for e in node.offline)
    return {
        'rescue_enabled': rescue,
        'reclamations': node.runtime.reclaimer.stats.reclamations,
        'offline_tokens_recomputed': offline_recompute,
        'requests_rescued': node.stats.requests_rescued,
        'pages_migrated':
            node.runtime.telemetry.snapshot()['pages_migrated'],
        'rescued_tokens_recomputed':
            (node.names['offB'].stats.tokens_recomputed if rescue else None),
    }


def run(*, mesh_sizes=(1, 2, 4, 8), warm: int = 24, steps: int = 48,
        gen: int = 120, out_path: str = 'results/shard_scale.json',
        bench_path: str = 'BENCH_shard.json') -> Dict:
    from repro.configs import get_config, reduced
    n_kv = reduced(get_config(ARCH), page_size=4).n_kv_heads
    scaling: List[Dict] = []
    ref_out = None
    for n in mesh_sizes:
        m = _measure_mesh(n, warm=warm, steps=steps, gen=gen)
        if m is None:
            print(f'mesh={n}: skipped (not enough virtual devices)')
            continue
        outs = m.pop('_outputs')
        if ref_out is None:
            ref_out = outs
        divergence = [
            next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
            for a, b in zip(ref_out, outs)]
        m['tokens_until_divergence'] = divergence
        # exact partitioning (width divides the kv-head axis) must not
        # change a single sampled token; oversharded widths may tie-flip
        # under bf16 and only record where
        if n_kv % n == 0 and any(d is not None for d in divergence):
            raise RuntimeError(
                f'mesh={n} drain diverged from mesh=1 at {divergence} '
                f'with exact kv-head partitioning ({n_kv} heads)')
        scaling.append(m)
        print(f"mesh={n}: {m['us_per_decode_step']:8.0f} us/step  "
              f"{m['decode_tokens_per_s']:7.1f} tok/s  "
              f"divergence={divergence}")

    on = _measure_rescue(True)
    off = _measure_rescue(False)
    for tag, r in (('rescue on ', on), ('rescue off', off)):
        print(f"{tag}: recompute={r['offline_tokens_recomputed']:3d} tok  "
              f"rescued={r['requests_rescued']}  "
              f"pages_migrated={r['pages_migrated']}")
    # hard gates (raise, not assert — must hold under -O)
    if on['requests_rescued'] < 1 or on['pages_migrated'] < 1:
        raise RuntimeError('burst rescued no victim cross-pool')
    if on['rescued_tokens_recomputed'] != 0:
        raise RuntimeError(
            f"rescued victims recomputed "
            f"{on['rescued_tokens_recomputed']} tokens (must be 0)")
    if on['offline_tokens_recomputed'] > off['offline_tokens_recomputed']:
        raise RuntimeError(
            f"rescue recompute tax {on['offline_tokens_recomputed']} > "
            f"truncation {off['offline_tokens_recomputed']}")

    result = {
        'mesh_scaling': scaling,
        'note': ('virtual CPU devices: mesh numbers measure GSPMD '
                 'partitioning overhead (expected to slow down); outputs '
                 f'bit-identical for widths dividing n_kv_heads={n_kv}, '
                 'oversharded widths may bf16-tie-flip (f32 is exact) — '
                 'see tokens_until_divergence'),
        'burst_recompute_tax': {'rescue_on': on, 'rescue_off': off},
    }
    os.makedirs(os.path.dirname(out_path) or '.', exist_ok=True)
    for path in (out_path, bench_path):
        with open(path, 'w') as f:
            json.dump(result, f, indent=1)
    return result


if __name__ == '__main__':
    import sys
    if '--smoke' in sys.argv:
        # short window, narrow meshes; full rescue gates (they're cheap)
        run(mesh_sizes=(1, 2), warm=12, steps=16, gen=64,
            out_path='results/shard_scale_smoke.json',
            bench_path='results/shard_scale_smoke.json')
        print('shard_scale smoke OK: mesh parity + zero-recompute rescue')
    else:
        run()
