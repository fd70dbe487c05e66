"""Chip smoke: serve the colocated Valve node on one TPU at published widths.

    python3 chip_smoke.py               # one chip (the default)
    python3 chip_smoke.py --four-chips  # only the four-chip mesh phase

One process, one chip.  Smoke, not a benchmark: the times it prints say
that the path ran, not how fast it is.

Default phases, all through the entry points a user calls:

1. **node** — ``build_node`` at published widths (online ``qwen3-0.6b``;
   offline ``qwen3-0.6b`` and ``internlm2-1.8b``, random weights from
   ``--seed``), the ``FrontendApp`` → ``AsyncNodeDriver`` →
   ``NodeOrchestrator`` → ``Engine`` → paged KV → Pallas path: a few
   streamed ``POST /v1/completions`` while a ``/v1/batches`` job backfills
   offline.  Every stream gets its tokens, the batch completes, at least one
   compute preemption happens and none hits a request twice, the runtime's
   invariants hold and no invalidation route outlives the drain.
2. **kernel** — the compiled Pallas paged decode kernel against the
   ``paged_attention_ref`` oracle (float32, highest precision) on the
   online engine's own KV pool and page tables, quarantine page 0 included.
3. **logits** — one full-width decode step with the Pallas kernel against
   the same step through the jnp oracle, same params and cache.

``--four-chips`` runs only: online ``qwen3-14b`` sharded over a 4-device
``('model',)`` mesh with offline ``internlm2-1.8b`` on the same runtime
(phase 1's assertions), and ``qwen3-14b`` cut to 4 layers served with
``mesh=None`` on chip 0 and on the mesh, logits compared.

The last stdout line is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before printing it.  Without a TPU it exits at once.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

ONLINE = 'qwen3-0.6b'
OFFLINE = ('qwen3-0.6b', 'internlm2-1.8b')
FOUR_CHIP_ONLINE = 'qwen3-14b'
FOUR_CHIP_OFFLINE = ('internlm2-1.8b',)

# bf16 bounds, stated before any run.  Kernel: its output is rounded to
# bf16 (2^-9 relative) and the probabilities enter the PV product at bf16
# precision at worst (2^-9 per term) — 1e-2 of the output scale leaves
# room for both.  Logits: the two attention paths differ by those
# roundings in every layer, carried through the residual stream to the
# unembedding — 5e-2 of the logit scale.
KERNEL_REL_BOUND = 1e-2
LOGITS_REL_BOUND = 5e-2


class CompileClock:
    """Seconds the process spends in XLA backend compiles (cache fetches
    included), from jax's own monitoring events."""

    EVENT = '/jax/core/compile/backend_compile_duration'

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1


class Phases:
    """Wall time and compile time of each phase, printed as it ends."""

    def __init__(self, compile_clock: CompileClock):
        self.cc = compile_clock

    def run(self, name, fn, *args, **kw):
        t0, c0, n0 = time.perf_counter(), self.cc.seconds, self.cc.programs
        out = fn(*args, **kw)
        print(f'[phase {name}] wall {time.perf_counter() - t0:.3f} s, '
              f'compile {self.cc.seconds - c0:.3f} s over '
              f'{self.cc.programs - n0} programs', flush=True)
        return out


def check(ok: bool, what: str) -> None:
    """A smoke assertion that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


def _prompt(rng, vocab: int, n: int):
    return rng.integers(1, vocab, n).tolist()


def _decode_path(eng) -> str:
    return 'pallas-paged-kernel' if eng.decode_kernel else 'jnp-oracle'


def warm_up(node, *, prompt_len: int, seed: int) -> None:
    """One request per engine, drained: compiles every engine's mixed
    prefill and pure-decode programs before anything is timed."""
    rng = np.random.default_rng(seed)
    vocab = min(e.mcfg.vocab_size for e in node.engines)
    for eng in node.engines:
        eng.submit(_prompt(rng, vocab, prompt_len), max_new_tokens=4)
    node.drain()


def serve_node_phase(node, *, n_streams: int = 4, prompt_len: int = 320,
                     max_tokens: int = 32, n_batch: int = 12,
                     batch_prompt_len: int = 256, batch_max_tokens: int = 48,
                     seed: int = 0):
    """Streams over the front-end while a batch job backfills; asserts the
    Valve contract and returns per-stream client-side timings."""
    from repro.core.events import PreemptionEvent
    from repro.serving.frontend.app import FrontendApp
    from repro.serving.frontend.driver import AsyncNodeDriver, clock_sleep
    from repro.serving.frontend.testing import ASGIClient

    rng = np.random.default_rng(seed)
    # batch items land on any offline engine: ids valid for all of them
    off_vocab = min(e.mcfg.vocab_size for e in node.offline)
    on_vocab = node.online.mcfg.vocab_size
    batch = [{'prompt': _prompt(rng, off_vocab, batch_prompt_len),
              'max_tokens': batch_max_tokens} for _ in range(n_batch)]
    prompts = [_prompt(rng, on_vocab, prompt_len) for _ in range(n_streams)]
    preempts0 = len(node.runtime.bus.events(PreemptionEvent))

    async def poll(client, bid, until):
        while True:
            st = (await client.get(f'/v1/batches/{bid}')).json()['status']
            if st == until:
                return
            if st in ('completed', 'cancelled'):
                raise AssertionError(f'batch reached {st}, not {until}')
            await clock_sleep(node.clock, 1e-3)

    async def stream(client, prompt):
        t0 = time.perf_counter()
        stamps, toks = [], []
        async with client.stream('POST', '/v1/completions',
                                 json={'prompt': prompt,
                                       'max_tokens': max_tokens,
                                       'stream': True}) as sr:
            check(sr.status == 200, f'stream status {sr.status}')
            async for ev in sr.events():
                if ev.done:
                    break
                tok = json.loads(ev.data)['choices'][0].get('token')
                if tok is not None:
                    toks.append(tok)
                    stamps.append(time.perf_counter())
        return t0, stamps, toks

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            client = ASGIClient(FrontendApp(driver))
            resp = await client.post('/v1/batches',
                                     json={'requests': batch})
            check(resp.status == 200, f'batch submit: {resp.body!r}')
            bid = resp.json()['id']
            # offline items hold the chip when the online burst arrives
            await poll(client, bid, 'in_progress')
            outs = await asyncio.gather(*(stream(client, p)
                                          for p in prompts))
            await poll(client, bid, 'completed')
            res = (await client.get(f'/v1/batches/{bid}/results')).json()
            return outs, res['results']

    outs, results = asyncio.run(scenario())
    for _, _, toks in outs:
        check(len(toks) == max_tokens, f'stream got {len(toks)} tokens')
    check(len(results) == n_batch
          and all(len(r['tokens']) == batch_max_tokens for r in results),
          'batch results incomplete')
    preempts = len(node.runtime.bus.events(PreemptionEvent)) - preempts0
    tel = node.runtime.telemetry.snapshot()
    check(preempts >= 1, 'no compute preemption observed')
    check(tel['max_preemptions_per_request'] <= 1,
          f'max preemptions per request {tel["max_preemptions_per_request"]}')
    node.runtime.check_invariants()
    node.pool.check_invariants()
    check(node.runtime.invalidation_routes() == [],
          f'routes left: {node.runtime.invalidation_routes()}')
    ttft = [stamps[0] - t0 for t0, stamps, _ in outs]
    tpot = [float(np.mean(np.diff(stamps))) for _, stamps, _ in outs]
    return {'streams': n_streams, 'tokens_per_stream': max_tokens,
            'batch_items': n_batch, 'preemptions': preempts,
            'max_preemptions_per_request': tel['max_preemptions_per_request'],
            'ttft_s': ttft, 'tpot_s': tpot}


def decode_batch_from_engine(node, *, n_rows: int, prompt_len: int,
                             seed: int):
    """Admit ``n_rows`` online requests through the node until each has
    prefilled; returns a decode batch over their real page tables (pool
    pages, quarantine page 0 past each table) and the KV lengths written.
    The requests stay live; ``node.drain()`` finishes them."""
    from repro.serving.scheduler import ReqState
    eng = node.online
    rng = np.random.default_rng(seed)
    rids = [eng.submit(_prompt(rng, eng.mcfg.vocab_size, prompt_len),
                       max_new_tokens=16) for _ in range(n_rows)]
    while any(eng.requests[r].state is not ReqState.RUNNING for r in rids):
        node.step()
    eng.flush_tokens()
    pt = np.zeros((eng.cfg.max_batch, eng.maxp), np.int32)
    pos = np.zeros((eng.cfg.max_batch,), np.int32)
    toks = np.zeros((eng.cfg.max_batch,), np.int32)
    for i, r in enumerate(rids):
        req = eng.requests[r]
        pt[i, :len(req.pages)] = req.pages
        pos[i] = len(req.context) - 1     # KV is written below this
        toks[i] = req.context[-1]
    return {'tokens': toks, 'positions': pos, 'page_table': pt}


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def kernel_phase(node, batch, *, seed: int):
    """Compiled Pallas paged kernel vs the float32 oracle on the online
    engine's layer-0 KV pool and real page tables."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.models.common import paged_attention_ref

    eng = node.online
    cfg = eng.mcfg
    pk, pv = eng.cache['k'][0], eng.cache['v'][0]
    lengths = jnp.asarray(np.maximum(batch['positions'], 1))
    pt = jnp.asarray(batch['page_table'])
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (pt.shape[0], cfg.n_heads, cfg.hd), jnp.bfloat16)
    got = jax.jit(lambda *a: paged_attention(*a, interpret=False))(
        q, pk, pv, pt, lengths)
    with jax.default_matmul_precision('highest'):
        want = jax.jit(paged_attention_ref)(
            q.astype(jnp.float32), pk.astype(jnp.float32),
            pv.astype(jnp.float32), pt, lengths)
    err = _rel_err(got, want)
    print(f'kernel: Pallas paged decode vs float32 oracle, shapes q '
          f'{tuple(q.shape)} pool {tuple(pk.shape)} tables '
          f'{tuple(pt.shape)}: max|err|/max|ref| = {err:.3e} '
          f'(bound {KERNEL_REL_BOUND:.0e})', flush=True)
    check(bool(np.isfinite(err)) and err <= KERNEL_REL_BOUND,
          f'kernel error {err}')
    return err


def decode_logits(model, params, cache, batch, *, use_pallas: bool,
                  mesh=None):
    """Logits of one decode step (no donation: the cache is reused)."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import SERVE_RULES, axis_rules

    def step(p, c, b):
        with axis_rules(mesh, SERVE_RULES):
            return model.decode_fn(p, c, b, use_pallas=use_pallas)[1]
    b = {k: jnp.asarray(v) for k, v in batch.items()}
    return np.asarray(jax.jit(step)(params, cache, b), np.float32)


def logits_phase(node, batch):
    """One full-width decode step: Pallas kernel path vs jnp oracle path."""
    eng = node.online
    pal = decode_logits(eng.model, eng.params, eng.cache, batch,
                        use_pallas=True)
    ref = decode_logits(eng.model, eng.params, eng.cache, batch,
                        use_pallas=False)
    err = _rel_err(pal, ref)
    agree = float(np.mean(pal.argmax(-1) == ref.argmax(-1)))
    print(f'logits: {eng.mcfg.name} decode step, Pallas vs oracle, '
          f'{pal.shape}: max|err|/max|ref| = {err:.3e} (bound '
          f'{LOGITS_REL_BOUND:.0e}); greedy agreement {agree:.3f}',
          flush=True)
    check(bool(np.all(np.isfinite(pal))), 'non-finite Pallas logits')
    check(err <= LOGITS_REL_BOUND, f'logits error {err}')
    return err


def print_timings(stats) -> None:
    print('smoke, not a benchmark: client-side TTFT / TPOT per stream (s)')
    for i, (a, b) in enumerate(zip(stats['ttft_s'], stats['tpot_s'])):
        print(f'  stream {i}: ttft {a:.4f}  tpot {b:.4f}')
    print(f'  preemptions {stats["preemptions"]}, max per request '
          f'{stats["max_preemptions_per_request"]}', flush=True)


def print_paths(node) -> None:
    for name, eng in node.names.items():
        print(f'engine {name}: decode attention {_decode_path(eng)}',
              flush=True)


def one_chip(phases: Phases, seed: int) -> None:
    from repro.configs import get_config
    from repro.launch.serve import build_node, print_device_bytes

    node = phases.run('build', build_node, get_config(ONLINE),
                      [get_config(a) for a in OFFLINE], seed=seed)
    print_device_bytes(node)
    print_paths(node)
    check(node.online.decode_kernel, 'online engine is not on the Pallas path')
    phases.run('warm-up', warm_up, node, prompt_len=64, seed=seed)
    stats = phases.run('node', serve_node_phase, node, seed=seed)
    print_timings(stats)
    batch = phases.run('admit', decode_batch_from_engine, node,
                       n_rows=node.online.cfg.max_batch, prompt_len=300,
                       seed=seed + 1)
    phases.run('kernel', kernel_phase, node, batch, seed=seed)
    phases.run('logits', logits_phase, node, batch)
    node.drain()
    node.runtime.check_invariants()


def node_on_mesh(mesh, online, offline, seed: int) -> None:
    """Phase 1 on a sharded node: every engine over ``mesh``, one gate per
    mesh device."""
    from repro.launch.serve import build_node, print_device_bytes
    node = build_node(online, offline, mesh=mesh, seed=seed)
    print_device_bytes(node)
    print_paths(node)
    n_dev = mesh.devices.size
    check(len(node.runtime.gates.gates) == n_dev,
          f'{len(node.runtime.gates.gates)} gates for {n_dev} devices')
    warm_up(node, prompt_len=64, seed=seed)
    stats = serve_node_phase(node, n_streams=3, prompt_len=256,
                             max_tokens=16, n_batch=6, batch_prompt_len=192,
                             batch_max_tokens=16, seed=seed)
    print_timings(stats)


def four_chips(phases: Phases, seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= 4, f'needs 4 devices: {jax.devices()}')
    mesh = make_mesh((4,), ('model',))
    phases.run('mesh-node', node_on_mesh, mesh, get_config(FOUR_CHIP_ONLINE),
               [get_config(a) for a in FOUR_CHIP_OFFLINE], seed)
    gc.collect()    # the sharded node's weights leave the chips first
    cut = dataclasses.replace(get_config(FOUR_CHIP_ONLINE), n_layers=4,
                              name=f'{FOUR_CHIP_ONLINE}-4layers')
    phases.run('mesh-vs-chip0', mesh_vs_single, mesh, cut, seed)


def mesh_vs_single(mesh, cfg, seed: int) -> float:
    """The same weights of ``cfg`` served with ``mesh=None`` on chip 0 and
    on the mesh; decode logits of the same prefilled requests compared."""
    import jax
    from repro.core.clock import VirtualClock
    from repro.core.runtime import RuntimeConfig, ValveRuntime
    from repro.launch.node import NodeOrchestrator
    from repro.models.api import build_model
    from repro.serving.engine import EngineConfig
    from repro.serving.kvpool import KVPool

    model = build_model(cfg)
    # 2048 tokens of pool: four 200-token prompts with room to decode
    pool_shape = dict(n_handles=8, pages_per_handle=256 // cfg.page_size,
                      page_size=cfg.page_size)
    sharded = model.init_params(
        jax.random.PRNGKey(seed),
        model.serve_shardings(mesh, KVPool(**pool_shape).n_pages)[0])
    logits = {}
    for label, m in (('chip0', None), ('mesh', mesh)):
        params = sharded if m is not None else \
            jax.device_put(sharded, jax.devices()[0])
        node = NodeOrchestrator(ValveRuntime(
            KVPool(**pool_shape), RuntimeConfig(mesh=m), clock=VirtualClock()))
        node.add_engine(cfg, EngineConfig(max_batch=4, max_seq=512,
                                          prefill_chunk=128, klass='online',
                                          mesh=m),
                        params=params, name=label)
        print_paths(node)
        batch = decode_batch_from_engine(node, n_rows=4, prompt_len=200,
                                         seed=seed + 2)
        eng = node.online
        logits[label] = decode_logits(model, eng.params, eng.cache, batch,
                                      use_pallas=eng.decode_kernel, mesh=m)
        node.drain()
        del node, eng, params
        gc.collect()
    err = _rel_err(logits['mesh'], logits['chip0'])
    agree = float(np.mean(logits['mesh'].argmax(-1)
                          == logits['chip0'].argmax(-1)))
    print(f'mesh vs chip 0: {cfg.name} decode logits max|err|/max|ref| = '
          f'{err:.3e} (bound {LOGITS_REL_BOUND:.0e}); greedy agreement '
          f'{agree:.3f}', flush=True)
    check(bool(np.all(np.isfinite(logits['mesh']))), 'non-finite mesh logits')
    check(err <= LOGITS_REL_BOUND, f'mesh logits error {err}')
    return err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the four-chip mesh phase')
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        print(f'chip_smoke: needs a TPU; JAX found {devices[0].platform}',
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / 'src'))
    from repro.launch.compile_cache import enable_compile_cache
    print(f'device: {devices[0].device_kind} x{len(devices)} '
          f'({devices[0].platform}); jax {jax.__version__}; compile cache '
          f'{enable_compile_cache()}', flush=True)
    cc = CompileClock()
    phases = Phases(cc)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(phases, args.seed)
    else:
        one_chip(phases, args.seed)
    stats = devices[0].memory_stats() or {}
    print(f'total wall {time.perf_counter() - t0:.3f} s, compile '
          f'{cc.seconds:.3f} s over {cc.programs} programs; device 0 '
          f'peak_bytes_in_use {stats.get("peak_bytes_in_use")}', flush=True)
    print(json.dumps({'ok': True, 'device': {
        'platform': devices[0].platform, 'kind': devices[0].device_kind,
        'count': len(devices)}}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
