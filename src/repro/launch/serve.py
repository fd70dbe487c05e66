"""Live online-offline colocation driver (one node).

One ONLINE engine (latency-critical, bursty arrivals) and N OFFLINE engines
(throughput batch work, **heterogeneous model configs**) share one KV pool
and one set of dispatch gates through the :class:`NodeOrchestrator`:

- online activity closes the offline compute gates (≤ 1 preemption per
  online request, wake after T_cool); offline backfills whenever the gates
  are open — the loop is driven from gate state, not ad-hoc alternation;
- online memory pressure reclaims offline handles (compute-first, quarantine
  remap); invalidations fan out to the owning engine's session (< 20-LOC
  callback, routed by allocation ownership — see ``docs/API.md``);
- MIAD keeps the online reservation tracking demand;
- every preemption/reclamation/wake-up is published on the runtime's typed
  event stream; the reported metrics derive from it (``runtime.telemetry``).

Reports TTFT / TPOT for online and tokens/s for offline — the same metrics
the paper's Fig. 10 uses; benchmarks/colocation_matrix.py runs the full
strategy grid in simulation, benchmarks/serve_throughput.py measures this
driver.

:func:`build_node` builds whatever model configs it is handed — the
published widths by default (``--http``); the scripted ``--steps`` demo,
the tests and CI hand it ``reduced()`` configs (:func:`demo_node`).

    # heterogeneous demo: online qwen3-0.6b + offline qwen3-0.6b AND
    # offline internlm2-1.8b (reduced) on one pool
    PYTHONPATH=src python -m repro.launch.serve --steps 400

    # pick the offline models explicitly (repeatable flag)
    PYTHONPATH=src python -m repro.launch.serve \\
        --offline-arch internlm2-1.8b --offline-arch qwen3-0.6b

    # HTTP front-end over the published-width models (a TPU-sized job)
    PYTHONPATH=src python -m repro.launch.serve --http --port 8080
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax
import numpy as np

from repro.configs import ModelConfig, get_config, reduced as reduce_cfg
from repro.core.clock import RealClock
from repro.core.runtime import RuntimeConfig, ValveRuntime
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.node import NodeOrchestrator
from repro.serving.engine import EngineConfig
from repro.serving.kvpool import KVPool

DEFAULT_OFFLINE_ARCHS = ('qwen3-0.6b', 'internlm2-1.8b')

# the reduced-width CPU demo: 2-layer d_model-64 models over a 4-token page
DEMO_PAGE_SIZE = 4
DEMO_SHAPE = dict(n_handles=24, pages_per_handle=8, max_seq=96,
                  prefill_chunk=16)


def build_node(online: ModelConfig, offline: Sequence[ModelConfig] = (), *,
               n_handles: int = 32, pages_per_handle: int = 16,
               max_seq: int = 512, prefill_chunk: int = 128,
               max_prefill_reqs: int = 4, piggyback_decode: bool = True,
               mesh=None, seed: int = 0, clock=None,
               idle_advance: float = 1e-3) -> NodeOrchestrator:
    """One node: an ``online`` engine + one offline engine per ``offline``
    config (heterogeneous models over one pool/runtime), at the widths the
    configs carry.  Every config must share one page size — the pool's.
    ``mesh`` (a ``jax.sharding.Mesh``) shards every engine over it and
    gives the runtime one dispatch gate per mesh device."""
    cfgs = [online, *offline]
    page_size = online.page_size
    if any(c.page_size != page_size for c in cfgs):
        raise ValueError(f'configs disagree on page size: '
                         f'{[(c.name, c.page_size) for c in cfgs]}')
    pool = KVPool(n_handles=n_handles, pages_per_handle=pages_per_handle,
                  page_size=page_size, reserved_handles=2)
    clock = clock or RealClock()
    rt = ValveRuntime(pool, RuntimeConfig(n_devices=1, mesh=mesh,
                                          t_cool_init=0.002), clock=clock)
    node = NodeOrchestrator(rt, idle_advance=idle_advance)

    def ecfg(klass: str) -> EngineConfig:
        return EngineConfig(max_batch=8, max_seq=max_seq,
                            prefill_chunk=prefill_chunk,
                            max_prefill_reqs=max_prefill_reqs,
                            piggyback_decode=piggyback_decode, klass=klass,
                            mesh=mesh)

    node.add_engine(online, ecfg('online'), seed=seed,
                    name=f'online:{online.name}')
    for i, cfg in enumerate(offline):
        node.add_engine(cfg, ecfg('offline'), seed=seed + i,
                        name=f'offline{i}:{cfg.name}')
    return node


def demo_node(*, arch: str = 'qwen3-0.6b',
              offline_archs: Sequence[str] = DEFAULT_OFFLINE_ARCHS,
              **kw) -> NodeOrchestrator:
    """:func:`build_node` over ``reduced()`` configs at the CPU demo's
    small pool and sequence budget (``kw`` overrides either)."""
    def small(a):
        return reduce_cfg(get_config(a), page_size=DEMO_PAGE_SIZE)
    return build_node(small(arch), [small(a) for a in offline_archs],
                      **{**DEMO_SHAPE, **kw})


def print_device_bytes(node: NodeOrchestrator) -> int:
    """Print the weight and KV-pool bytes each engine holds on its devices
    (every engine keeps a KV array over the whole pool); returns the sum."""
    total = 0
    for name, eng in node.names.items():
        params, cache = (sum(x.nbytes for x in jax.tree.leaves(t))
                         for t in (eng.params, eng.cache))
        total += params + cache
        print(f'{name}: params {params / 2**30:.3f} GiB, '
              f'KV cache {cache / 2**30:.3f} GiB')
    print(f'device bytes held by the node, all devices: '
          f'{total / 2**30:.3f} GiB')
    return total


def serve_demo(*, arch: str = 'qwen3-0.6b',
               offline_archs: Sequence[str] = DEFAULT_OFFLINE_ARCHS,
               steps: int = 400, online_rate: float = 0.08,
               burst_every: int = 120, seed: int = 0, clock=None,
               quiet: bool = False, max_prefill_reqs: int = 4,
               piggyback_decode: bool = True,
               node: Optional[NodeOrchestrator] = None):
    """Drive the reduced-width demo node for ``steps`` scheduler ticks;
    returns metrics.

    A prebuilt ``node`` takes precedence: the build kwargs (``arch``,
    ``offline_archs``, ``max_prefill_reqs``, ``piggyback_decode``,
    ``clock``) only apply when this function builds the node itself.
    """
    rng = np.random.default_rng(seed)
    node = node or demo_node(arch=arch, offline_archs=offline_archs,
                             seed=seed, clock=clock,
                             max_prefill_reqs=max_prefill_reqs,
                             piggyback_decode=piggyback_decode)
    online_eng = node.online

    # offline backlog: long prompts, long generations, spread round-robin
    # across the (heterogeneous) offline engines
    for i in range(6 * len(node.offline)):
        eng = node.offline[i % len(node.offline)]
        eng.submit(rng.integers(1, eng.mcfg.vocab_size, 24).tolist(),
                   max_new_tokens=24)

    for t in range(steps):
        # bursty online arrivals: poisson background + periodic spike
        # (an offline-only prebuilt node simply gets no arrivals)
        n_new = rng.poisson(online_rate) + (3 if t % burst_every == 0 else 0)
        for _ in range(n_new if online_eng is not None else 0):
            online_eng.submit(
                rng.integers(1, online_eng.mcfg.vocab_size, 12).tolist(),
                max_new_tokens=8)
        node.step()
    # arrivals over: drain the remaining (mostly offline) backlog so the
    # throughput metrics reflect completed work, not a truncated run
    node.drain()

    # event-log invariants (≤1 preemption/request, wakeups==gate-enables,
    # §5 ordering) + the published-event census from the typed stream
    node.runtime.check_invariants()
    metrics = node.metrics()
    metrics['events'] = dict(node.runtime.bus.published)
    metrics['live_invalidation_routes'] = \
        len(node.runtime.invalidation_routes())
    if not quiet:
        for k, v in metrics.items():
            if k == 'engines':
                for name, em in v.items():
                    print(f'  engine {name}: {em}')
            else:
                print(f'  {k}: {v}')
    return metrics


def serve_http(*, arch: str = 'qwen3-0.6b',
               offline_archs: Sequence[str] = DEFAULT_OFFLINE_ARCHS,
               host: str = '127.0.0.1', port: int = 8080,
               seed: int = 0) -> None:
    """Run the async serving front-end over a live node: OpenAI-style
    ``POST /v1/completions`` (SSE streaming) + the ``/v1/batches`` offline
    batch-job API, one event loop owning the front end and one worker
    thread the node's steps (docs/API.md § Serving endpoints).

        PYTHONPATH=src python -m repro.launch.serve --http --port 8080
        curl -N localhost:8080/v1/completions -d \\
            '{"prompt": [5, 7, 11], "max_tokens": 8, "stream": true}'
    """
    import asyncio

    from repro.serving.frontend.app import FrontendApp
    from repro.serving.frontend.driver import AsyncNodeDriver
    from repro.serving.frontend.http import serve_asgi

    node = build_node(get_config(arch),
                      [get_config(a) for a in offline_archs], seed=seed)
    print_device_bytes(node)

    async def _main() -> None:
        async with AsyncNodeDriver(node) as driver:
            server = await serve_asgi(FrontendApp(driver), host, port)
            print(f'serving on http://{host}:{server.port}  '
                  f'(online {arch}, offline {", ".join(offline_archs)})')
            try:
                await server.serve_forever()
            except asyncio.CancelledError:
                pass
            finally:
                await server.stop()

    try:
        asyncio.run(_main())
    except KeyboardInterrupt:
        print('shutting down')


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='qwen3-0.6b',
                    help='online engine architecture')
    ap.add_argument('--offline-arch', action='append', default=None,
                    help='offline engine architecture (repeatable; default: '
                         f'{" + ".join(DEFAULT_OFFLINE_ARCHS)})')
    ap.add_argument('--steps', type=int, default=400)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--http', action='store_true',
                    help='serve the HTTP front-end (SSE streaming + batch '
                         'jobs) instead of running the scripted demo')
    ap.add_argument('--host', default='127.0.0.1')
    ap.add_argument('--port', type=int, default=8080)
    args = ap.parse_args()
    offline_archs = tuple(args.offline_arch or DEFAULT_OFFLINE_ARCHS)
    enable_compile_cache()
    if args.http:
        serve_http(arch=args.arch, offline_archs=offline_archs,
                   host=args.host, port=args.port, seed=args.seed)
    else:
        serve_demo(arch=args.arch, offline_archs=offline_archs,
                   steps=args.steps, seed=args.seed)


if __name__ == '__main__':
    main()
