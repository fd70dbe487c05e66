"""One run of one benchmark cell: set up, measure, check, report.

The system under test is the colocated node as ``repro.launch.serve.
build_node`` builds it, driven through its served entry: in-process
``FrontendApp`` ``POST /v1/completions`` SSE streams for online requests
and ``/v1/batches`` jobs for offline work, over ``AsyncNodeDriver`` →
``NodeOrchestrator.step`` → ``Engine`` → paged KV.

The benchmark takes from the program only that node; its own spans wrap
the calls into each layer (each engine instance's ``step`` and its
scheduler's ``schedule``, the runtime's ``tick``, the driver's submit and
flush).  With ``trace`` the same spans also go into the profiler's trace.

Everything a cell is made of is found by name: ``BENCHMARK.json`` →
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json`` and
``bench/metrics/<metric>.py``; each served model's program config,
reference and counts → ``bench/archs/<architecture>.py``.

A config that spans several chips declares its mesh, axis names (the
program's: ``data``, ``model``) to sizes in order, e.g. ``"mesh":
{"model": 4}``.  The node is built over it, and the reference lays its
weights over the same devices.  Without the key the node runs on one
device, with ``mesh=None``.
"""
from __future__ import annotations

import asyncio
import contextlib
import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import archs        # noqa: E402
import client       # noqa: E402
import e2e          # noqa: E402
import reference    # noqa: E402
import workload     # noqa: E402

RAMP_S = 5.0        # traffic before the window: the offline backlog settles
TRACE_S = 10.0      # the traced stretch: the last seconds of the window
DRAIN_S = 60.0      # how long past the window requests due in it may take
SAMPLE_ONLINE = 6   # finished online requests compared with the reference
SAMPLE_OFFLINE = 4  # finished offline items compared with the reference
TRACE_DIR = ROOT / '.bench_out' / 'trace'


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def mesh_size(cfg: dict) -> int:
    """Devices the config's ``mesh`` spans: 1 without one."""
    return math.prod(cfg.get('mesh', {}).values())


def load_cell(name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``.  Its config's mesh, its
    config's ``chips`` and the cell's ``chips`` have to agree."""
    bench = load_json(ROOT / 'BENCHMARK.json')
    cells = {w['name']: w for w in bench['workloads']}
    if name not in cells:
        raise KeyError(f'no workload {name!r}; known: {sorted(cells)}')
    w = cells[name]
    cfg_entry = next(c for c in bench['configs'] if c['name'] == w['config'])
    reports = [m for m in bench['end_to_end']
               if name in m.get('workloads', [name])]
    names = {m['name'] for m in reports}
    layer = [m for m in bench['per_layer']
             if name in m.get('workloads', [name]) and m['moves'] in names]
    cfg = load_json(ROOT / cfg_entry['file'])
    chips = (mesh_size(cfg), cfg['chips'], w['chips'])
    if len(set(chips)) != 1:
        raise ValueError(
            f'cell {name}: config {cfg_entry["file"]} declares a mesh of '
            f'{chips[0]} device(s) ({cfg.get("mesh", "no mesh key")}) and '
            f'chips {chips[1]}; BENCHMARK.json gives the cell chips '
            f'{chips[2]}: the three have to agree')
    return Cell(name, int(w['chips']), cfg, workload.load_mix(w['traffic']),
                reports, layer)


def node_mesh(cfg: dict):
    """The program's mesh over the first devices for the config's
    ``mesh``, or None where it declares none (a one-device mesh would
    move the engines off their single-device path)."""
    if 'mesh' not in cfg:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh(tuple(cfg['mesh'].values()), tuple(cfg['mesh']))


def model_config(entry: dict, page_size: int):
    """The program's ModelConfig for one served model of a config file, as
    its architecture's module gives it."""
    from repro.configs import ModelConfig
    hf = entry['config']
    return ModelConfig(name=entry['model'],
                       **archs.of(hf).program_config(hf, page_size))


class GcClock:
    """Python's cyclic collections and the seconds they hold the host."""

    def __init__(self):
        self.seconds = 0.0
        self.full = 0
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == 'start':
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.full += info['generation'] == 2


class CompileClock:
    """Seconds and programs the process spends in XLA backend compiles
    (cache fetches included), from jax's monitoring events."""

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


@dataclass
class StepSpan:
    """One engine step that dispatched."""
    engine: str
    klass: str
    kind: str                   # 'mixed' | 'decode'
    t0: float
    t1: float
    tokens: int                 # tokens the step produced
    prefill: tuple = ()         # (start, length) of each prefill row
    live: tuple = ()            # attended tokens of each decode row


@dataclass
class Run:
    """What one run recorded; the per-layer readers take it as input."""
    cell: Cell
    seconds: float
    chips: int = 1              # the devices the node spans
    w0: float = 0.0
    w1: float = 0.0
    online: List[e2e.OnlineRecord] = field(default_factory=list)
    steps: List[StepSpan] = field(default_factory=list)
    intake: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, Dict[str, dict]] = field(default_factory=dict)
    engines: Dict[str, dict] = field(default_factory=dict)  # label → model
    offline_done: List[dict] = field(default_factory=list)
    offline_attempted: int = 0
    offline_failed: int = 0
    trace: Optional[object] = None
    traced: tuple = (0.0, 0.0)  # host clock of the traced stretch
    peaks: Optional[dict] = None

    def steps_in(self, lo: float, hi: float, **match) -> List[StepSpan]:
        return [s for s in self.steps if lo <= s.t0 and s.t1 <= hi
                and all(getattr(s, k) == v for k, v in match.items())]


class Clock:
    """The host clock the whole run is timed on, and waits on it.  A
    virtual clock (tests) advances instead of sleeping."""

    def __init__(self, clock):
        self.clock = clock
        self.virtual = getattr(clock, 'virtual', False)

    def now(self) -> float:
        return self.clock.now()

    async def until(self, t: float) -> None:
        if self.virtual:
            if t > self.clock.now():
                self.clock.advance_to(t)
            await asyncio.sleep(0)
        else:
            await asyncio.sleep(max(0.0, t - self.clock.now()))


def instrument(run: Run, node, clock: Clock, annotate: bool) -> None:
    """Wrap each engine's ``step`` and ``sched.schedule`` and the runtime's
    ``tick`` in the benchmark's spans (instance attributes: the program's
    code is untouched)."""
    span = _span_factory(annotate)
    for label, eng in node.names.items():
        _wrap_engine(run, label, eng, clock, span)
    tick = node.runtime.tick

    def traced_tick():
        with span('runtime.tick'):
            return tick()
    node.runtime.tick = traced_tick


def _span_factory(annotate: bool):
    if not annotate:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _wrap_engine(run: Run, label: str, eng, clock: Clock, span) -> None:
    schedule, step = eng.sched.schedule, eng.step
    box: list = [None]

    def traced_schedule(requests, *a, **kw):
        b = schedule(requests, *a, **kw)
        box[0] = (b, tuple(len(requests[s.req_id].context)
                           for s in b.decode))
        return b

    name = f'engine.step:{label}'

    def traced_step():
        box[0] = None
        tok0 = eng.stats.tokens_generated
        t0 = clock.now()
        with span(name):
            out = step()
        t1 = clock.now()
        if out and box[0] is not None:
            b, live = box[0]
            run.steps.append(StepSpan(
                label, eng.cfg.klass, 'mixed' if b.prefill else 'decode',
                t0, t1, eng.stats.tokens_generated - tok0,
                tuple((p.start, p.length) for p in b.prefill), live))
        return out

    eng.sched.schedule = traced_schedule
    eng.step = traced_step


def instrument_driver(run: Run, driver, clock: Clock, annotate: bool):
    span = _span_factory(annotate)
    submit, flush, poll = (driver.submit_stream, driver._flush_streams,
                           driver.batches.poll)

    def traced_submit(prompt, max_new_tokens=32):
        t = clock.now()
        with span('driver.submit'):
            s = submit(prompt, max_new_tokens)
        run.intake[s.req_id] = t
        return s

    def traced_flush():
        with span('driver.flush'):
            return flush()

    def traced_poll():
        with span('driver.batches_poll'):
            return poll()
    driver.submit_stream = traced_submit
    driver._flush_streams = traced_flush
    driver.batches.poll = traced_poll


def snapshot(node) -> Dict[str, dict]:
    import dataclasses
    return {label: dataclasses.asdict(eng.stats)
            for label, eng in node.names.items()}


def warm_up(node, seed: int) -> None:
    """Compile (or load from the cache) every program the window runs:
    each engine's mixed and pure-decode dispatch and its sampler."""
    rng = np.random.default_rng(seed)
    for eng in node.engines:
        eng.submit(rng.integers(1, eng.mcfg.vocab_size, 40).tolist(),
                   max_new_tokens=4)
    node.drain()


async def _stream(app, rec: e2e.OnlineRecord, clock: Clock) -> None:
    """One online request over SSE; frames are timed as the app sends."""
    rec.t_send = clock.now()

    def on_frame(chunk):
        tok = chunk['choices'][0].get('token')
        if tok is not None:
            now = clock.now()
            if rec.t_first is None:
                rec.t_first, rec.rid = now, chunk['id']
            rec.t_last = now
            rec.tokens.append(int(tok))
    try:
        status, _ = await client.request(
            app, 'POST', '/v1/completions',
            {'prompt': list(rec.prompt), 'max_tokens': rec.want,
             'stream': True}, on_frame=on_frame)
        if status != 200:
            rec.status = f'failed: http {status}'
        elif len(rec.tokens) != rec.want:
            rec.status = f'failed: {len(rec.tokens)} of {rec.want} tokens'
        else:
            rec.status = 'ok'
    except Exception as e:      # a failed request is counted, not fatal
        rec.status = f'failed: {type(e).__name__}: {e}'


async def _feed_offline(app, items, run: Run, clock: Clock, until: float,
                        jobs: Dict[str, list]) -> None:
    """Keep at least ``items.min_queued`` offline items queued until
    ``until``, in jobs of ``items.job_items``."""
    live: List[str] = []
    while clock.now() < until:
        queued = 0
        for jid in list(live):
            _, st = await client.request(app, 'GET', f'/v1/batches/{jid}')
            queued += st['request_counts']['queued']
            if st['status'] in ('completed', 'cancelled'):
                live.remove(jid)
        if queued < items.min_queued:
            batch = items.take(items.job_items)
            inside = run.w0 <= clock.now() < run.w1
            status, job = await client.request(app, 'POST', '/v1/batches',
                                               {'requests': batch})
            if inside:
                run.offline_attempted += len(batch)
            if status == 200:
                jid = job['id']
                jobs[jid] = batch
                live.append(jid)
            elif inside:
                run.offline_failed += len(batch)
            continue
        await clock.until(clock.now() + 0.1)


async def drive(node, run: Run, mix: dict, seed: int, clock: Clock, *,
                trace: bool, compile_clock=None, gc_clock=None,
                drain_s: float = DRAIN_S) -> None:
    """Ramp, window, and the wait for requests due in it."""
    from repro.serving.frontend.app import FrontendApp
    from repro.serving.frontend.driver import AsyncNodeDriver

    on_vocab = node.online.mcfg.vocab_size
    ramp = workload.online_schedule(mix, RAMP_S, seed, vocab=on_vocab,
                                    part=0)
    window = workload.online_schedule(mix, run.seconds, seed,
                                      vocab=on_vocab, part=1)
    items = workload.OfflineItems(
        mix, seed, vocab=min([e.mcfg.vocab_size for e in node.offline]
                             or [on_vocab]))
    loop = asyncio.get_running_loop()
    async with AsyncNodeDriver(node) as driver:
        instrument_driver(run, driver, clock, trace)
        app = FrontendApp(driver)
        start = clock.now()
        run.w0 = start + RAMP_S
        run.w1 = run.w0 + run.seconds
        plan = ([(start + a.t, a, False) for a in ramp.arrivals]
                + [(run.w0 + a.t, a, True) for a in window.arrivals])
        jobs: Dict[str, list] = {}
        tasks = []
        if items:
            tasks.append(loop.create_task(_feed_offline(
                app, items, run, clock, run.w1, jobs)))
        marks = loop.create_task(_marks(node, run, clock, trace,
                                        compile_clock, gc_clock))
        streams = []
        for due, a, inside in plan:
            await clock.until(due)
            rec = e2e.OnlineRecord(due=due, want=a.max_tokens,
                                   prompt=a.prompt, in_window=inside)
            run.online.append(rec)
            streams.append(loop.create_task(_stream(app, rec, clock)))
        await marks
        # stopping the profiler holds the loop; the drain starts after it
        deadline = run.w1 + drain_s + run.counters.get('trace_stop_s', 0.0)
        while clock.now() < deadline and not all(s.done() for s in streams):
            await clock.until(min(deadline, clock.now() + 0.05))
        for s in streams:
            if not s.done():
                s.cancel()
        for t in tasks:
            await t
        # the window is over: stop the offline work and read what finished
        for jid in jobs:
            await client.request(app, 'POST', f'/v1/batches/{jid}/cancel')
        for jid, batch in jobs.items():
            _, res = await client.request(app, 'GET',
                                          f'/v1/batches/{jid}/results')
            for r in res['results']:
                if r['status'] == 'completed':
                    run.offline_done.append(
                        {'prompt': batch[r['index']]['prompt'],
                         'tokens': r['tokens'], 'model': r['engine']})
        while node.has_work() and clock.now() < deadline + drain_s:
            await clock.until(clock.now() + 0.05)
        await asyncio.gather(*streams, return_exceptions=True)


async def _marks(node, run: Run, clock: Clock, trace: bool,
                 compile_clock, gc_clock=None) -> None:
    """Counters at the window's edges.  The traced stretch is the last
    ``TRACE_S`` of the window: stopping the profiler blocks the host for
    seconds, which must fall after the window, not inside it."""
    import jax
    await clock.until(run.w0)
    run.counters['w0'] = snapshot(node)
    run.counters['w0_real'] = time.monotonic()
    if compile_clock is not None:
        run.counters['compiles_w0'] = {'n': compile_clock.programs}
    if gc_clock is not None:
        run.counters['gc_w0'] = {'s': gc_clock.seconds, 'full': gc_clock.full}
    if trace:
        await clock.until(run.w1 - min(TRACE_S, run.seconds))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation('bench.window'):
            pass
        run.traced = (clock.now(), 0.0)
    await clock.until(run.w1)
    run.counters['w1'] = snapshot(node)
    if compile_clock is not None:
        run.counters['compiles_w1'] = {'n': compile_clock.programs}
    if gc_clock is not None:
        run.counters['gc_w1'] = {'s': gc_clock.seconds, 'full': gc_clock.full}
    if trace:
        run.traced = (run.traced[0], clock.now())
        with jax.profiler.TraceAnnotation('bench.window'):
            pass
        t = clock.now()
        jax.profiler.stop_trace()
        run.counters['trace_stop_s'] = clock.now() - t


def stalls(run: Run) -> str:
    """Where the host was held longest in the window: the longest engine
    step, the longest time between steps, and the latest send."""
    steps = sorted(run.steps_in(run.w0, run.w1), key=lambda s: s.t0)
    if not steps:
        return 'no engine step in the window'
    long = max(steps, key=lambda s: s.t1 - s.t0)
    gap, at = max(((b.t0 - a.t1, a.t1) for a, b in zip(steps, steps[1:])),
                  default=(0.0, run.w0))
    late = max((r for r in run.online if r.in_window and r.t_send),
               key=lambda r: r.t_send - r.due, default=None)
    out = (f'longest step {1e3 * (long.t1 - long.t0):.1f} ms '
           f'({long.engine} {long.kind}) at window +{long.t0 - run.w0:.2f} s; '
           f'longest time between steps {1e3 * gap:.1f} ms at window '
           f'+{at - run.w0:.2f} s')
    if late is not None:
        out += (f'; latest send {1e3 * (late.t_send - late.due):.1f} ms, due '
                f'at window +{late.due - run.w0:.2f} s')
    return out


def gate_state(node) -> str:
    """What decided the offline harvest over the run: gate wake-ups, steps
    on which offline work waited behind closed gates, and T_cool."""
    m = node.metrics()
    return (f'offline wake-ups {m["offline_wakeups"]}, gated skips '
            f'{m["gated_skips"]}, offline dispatches {m["offline_dispatches"]}, '
            f'reclamations {m["reclamations"]}, T_cool at the end '
            f'{1e3 * node.runtime.lifecycle.t_cool:.1f} ms')


def guarantees(node) -> Dict[str, list]:
    """The paper's guarantees, read after the drain: ``[value, limit]``."""
    tel = node.runtime.telemetry.snapshot()
    broken = 0
    for check in (node.runtime.check_invariants, node.pool.check_invariants):
        try:
            check()
        except AssertionError as e:
            broken += 1
            print(f'invariant broken: {check.__qualname__}: {e!r}',
                  file=sys.stderr)
    return {'preemptions_per_request': [tel['max_preemptions_per_request'], 1],
            'broken_invariants': [broken, 0],
            'routes_left': [len(node.runtime.invalidation_routes()), 0]}


def pick(items: list, n: int, rng, *, key) -> list:
    """The longest by ``key`` and ``n - 1`` others drawn by ``rng``."""
    if not items:
        return []
    chosen = [max(range(len(items)), key=lambda i: key(items[i]))]
    rest = [i for i in rng.permutation(len(items)) if i not in chosen]
    chosen += rest[:max(0, n - len(chosen))]
    return [items[i] for i in chosen]


# What the reference reads over a model's served tokens: the widest gap
# of a served token's logit below the reference's best, and the mean gap.
# Only the numbers in COMPARED decide ``correct`` (PERF.md, section 2).
READINGS = {'gap': np.max, 'gap_mean': np.mean}
COMPARED = ('gap_mean',)


def compare(run: Run, cfg: dict, mix: dict, model_seed: int, seed: int,
            control: bool = False, mesh=None) -> Dict[str, dict]:
    """The readings of each model's sample of served tokens (and the
    control's, with ``control``), each reference's weights laid over
    ``mesh``.  Offline models are compared where the mix sends offline
    work."""
    rng = np.random.default_rng([seed, 3])
    out = {}
    done = [r for r in run.online if r.ok and r.in_window]
    sample = pick(done, SAMPLE_ONLINE, rng,
                  key=lambda r: (len(r.tokens), len(r.prompt)))
    groups = [('online', cfg['online'], model_seed,
               [(r.prompt, r.tokens) for r in sample])]
    for i, off in enumerate(cfg['offline'] if mix.get('offline') else []):
        items = [r for r in run.offline_done if r['model'] == off['model']]
        sample = pick(items, SAMPLE_OFFLINE, rng,
                      key=lambda r: len(r['tokens']))
        groups.append((f'offline{i}' if i else 'offline', off,
                       model_seed + i,
                       [(r['prompt'], r['tokens']) for r in sample]))
    for name, entry, mseed, reqs in groups:
        if not reqs:    # nothing finished to compare: fails every limit
            out[name] = {'program': dict.fromkeys(READINGS, math.inf),
                         'control': dict.fromkeys(READINGS, math.inf),
                         'tokens': 0, 'requests': 0}
            continue
        ref = reference.Reference(entry['config'], mseed, mesh=mesh)
        gaps, ctl = [], []
        for prompt, toks in reqs:
            g, c = ref.gaps(prompt, toks, control=control)
            gaps.append(g)
            ctl.append(c)
        del ref
        gc.collect()
        gaps, ctl = np.concatenate(gaps), np.concatenate(ctl)
        out[name] = {
            'program': {k: float(f(gaps)) for k, f in READINGS.items()},
            'control': {k: float(f(ctl)) for k, f in READINGS.items()},
            'tokens': int(gaps.size), 'requests': len(reqs)}
    return out


def metric_reader(name: str) -> Callable:
    path = BENCH / 'metrics' / f'{name}.py'
    spec = importlib.util.spec_from_file_location(
        'bench_metric_' + name.replace('.', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def live_bytes(devices) -> List[int]:
    """Bytes of the live jax arrays on each of ``devices``."""
    import jax
    held = dict.fromkeys(devices, 0)
    for a in jax.live_arrays():
        for sh in a.addressable_shards:
            if sh.device in held:
                held[sh.device] += sh.data.nbytes
    return list(held.values())


def peak_bytes(devices) -> List[int]:
    """Each device's peak bytes in use, where the backend reports it."""
    return [int((d.memory_stats() or {}).get('peak_bytes_in_use', 0))
            for d in devices]


def device_info(devices) -> dict:
    return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices),
            'memory_peak_bytes': max(peak_bytes(devices))}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, adapt: Optional[Callable] = None,
             on_node: Optional[Callable] = None, clock=None,
             out=None, control: bool = False) -> dict:
    """One run: returns the result dict (also printed, last on stdout).

    With ``control`` the control's readings take the program's place in
    ``checks``, so ``correct`` says whether the control passes the limits
    (it must not); the program's readings go under ``readings``."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_node

    out = out or sys.stdout
    cell = load_cell(name)
    cfg, mix = cell.config, cell.mix
    if adapt is not None:
        cfg, mix = adapt(cfg, mix)
    cache_dir = enable_compile_cache()
    # every program, however small, comes from the cache after the first run
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    cc = CompileClock()
    gcc = GcClock()
    mesh = node_mesh(cfg)
    devices = jax.devices()[:mesh_size(cfg)]
    peaks = None
    if devices[0].platform == 'tpu':
        table = load_json(BENCH / 'peaks.json')
        if devices[0].device_kind not in table:
            raise KeyError(f'no peaks for device kind '
                           f'{devices[0].device_kind!r} in peaks.json')
        peaks = table[devices[0].device_kind]
    model_seed = seed % (2 ** 31 - 1024)
    page = cfg['page_size']
    t_build = time.monotonic()
    node = build_node(model_config(cfg['online'], page),
                      [model_config(o, page) for o in cfg['offline']],
                      seed=model_seed, clock=clock, mesh=mesh,
                      **cfg['node'])
    if on_node is not None:
        on_node(node)
    t_warm = time.monotonic()
    warm_up(node, seed)
    t_ramp = time.monotonic()
    hclock = Clock(node.clock)
    run = Run(cell, float(seconds), chips=len(devices), peaks=peaks)
    run.engines = {label: (cfg['online'] if eng.cfg.klass == 'online'
                           else cfg['offline'][i - 1])['config']
                   for i, (label, eng) in enumerate(node.names.items())}
    instrument(run, node, hclock, trace)
    compiles_setup = cc.programs
    asyncio.run(drive(node, run, mix, seed, hclock, trace=trace,
                      compile_clock=cc, gc_clock=gcc))
    setup_s = run.counters['w0_real'] - t_start
    checks = guarantees(node)
    gate = gate_state(node)
    device = device_info(devices)
    peak_each = peak_bytes(devices)
    window_compiles = (run.counters['compiles_w1']['n']
                       - run.counters['compiles_w0']['n'])
    del node
    gc.collect()

    om = e2e.online_metrics(run.online)
    e2e_values = {
        'ttft_p90_ms': om['ttft_p90_ms'], 'ttft_p50_ms': om['ttft_p50_ms'],
        'tpot_p90_ms': om['tpot_p90_ms'], 'setup_s': setup_s}
    say = functools.partial(print, file=sys.stderr, flush=True)
    say(f'cell {name}: seed {seed}, window {seconds} s, trace {int(trace)}')
    say(f'online requests due in the window: {om["n"]} (failed '
        f'{om["failed"]}); with >= 2 tokens: {om["n_tpot"]}; in the ramp '
        f'{sum(not r.in_window for r in run.online)}')
    for r in [r for r in run.online if r.in_window and not r.ok][:5]:
        say(f'failed online request due at window +{r.due - run.w0:.3f} s, '
            f'{len(r.prompt)} prompt tokens: {r.status}')
    say(f'set-up: to the build {t_build - t_start:.3f} s, build '
        f'{t_warm - t_build:.3f} s, warm-up {t_ramp - t_warm:.3f} s, ramp '
        f'{run.counters["w0_real"] - t_ramp:.3f} s')
    say(f'generator lateness p90: {om["send_late_p90_ms"]:.3f} ms')
    say(stalls(run))
    say(f'offline items attempted in the window: {run.offline_attempted}; '
        f'finished in the run: {len(run.offline_done)}; offline tokens per '
        f'second in the window: '
        f'{e2e.tokens_in_window(run.steps, "offline", run.w0, run.w1) / run.seconds:.6g}')
    say(gate)
    say(f'compilations inside the window: {window_compiles}; in set-up: '
        f'{compiles_setup}; compile cache {cache_dir}')
    g0, g1 = run.counters['gc_w0'], run.counters['gc_w1']
    say(f'memory peak per device: {peak_each}')
    say(f'garbage collection inside the window: {g1["s"] - g0["s"]:.3f} s, '
        f'{g1["full"] - g0["full"]} full collections')
    say('end to end: ' + ', '.join(f'{k} {v:.6g}'
                                   for k, v in e2e_values.items()))
    metrics = {}
    if trace:
        say(f'profiler stop held the host '
            f'{run.counters["trace_stop_s"]:.1f} s after the window')
        t_read = time.monotonic()
        dt = _devtrace()
        run.trace = dt.load(dt.find_xplane(str(TRACE_DIR)))
        device['busy_s'] = dt.busy_s(run.trace)
        device['window_s'] = run.trace.window_s
        for m in cell.per_layer:
            v = metric_reader(m['name'])(run)
            say(f'per-layer {m["name"]}: {v}')
            if v is None:
                say(f'per-layer metric {m["name"]}: nothing to read in this '
                    f'run; left out')
                continue
            metrics[m['name']] = {'value': float(v), 'unit': m['unit']}
        say(f'trace read in {time.monotonic() - t_read:.1f} s')
    else:
        for m in cell.end_to_end:
            metrics[m['name']] = {'value': float(e2e_values[m['name']]),
                                  'unit': m['unit']}

    # the node's buffers have to be gone: a reference the size of the
    # served model does not fit beside it
    say(f'device bytes live as the reference starts, per device: '
        f'{live_bytes(devices)}')
    t_ref = time.monotonic()
    result_control = {}
    gaps = compare(run, cfg, mix, model_seed, seed, control, mesh)
    say(f'reference compared {sum(g["tokens"] for g in gaps.values())} '
        f'served tokens of {sum(g["requests"] for g in gaps.values())} '
        f'requests in {time.monotonic() - t_ref:.1f} s')
    limits = cfg.get('limits', {})
    # with ``control`` the control stands in the program's place: its
    # readings are the ones held to the limits
    judged = 'control' if control else 'program'
    for model, g in gaps.items():
        say(f'reference readings, {model}: ' + ', '.join(
            f'{k} {v:.6g}' for k, v in g['program'].items()))
        for k in COMPARED:
            checks[f'{k}.{model}'] = [g[judged][k],
                                      limits.get(f'{k}.{model}')]
        if control:
            for k, v in g['control'].items():
                say(f'control {k}.{model}: {v}')
                result_control[f'{k}.{model}'] = v
    checks['online_failed'] = [om['failed'], 0]
    checks['offline_failed'] = [run.offline_failed, 0]
    correct = all(lim is not None and val <= lim
                  for val, lim in checks.values())
    for m in metrics.values():
        if not math.isfinite(m['value']):
            m['value'], correct = None, False
    result = {'correct': bool(correct),
              'attempted': om['n'] + run.offline_attempted,
              'failed': om['failed'] + run.offline_failed,
              'metrics': metrics, 'device': device}
    if control:
        result['control'] = result_control
        result['readings'] = {f'{k}.{model}': v for model, g in gaps.items()
                              for k, v in g['program'].items()}
    if trace:
        result['breakdown'] = {'device_ops': dt.top_ops(run.trace),
                               'idle_gaps': dt.idle_by_host(run.trace)}
    for k, (v, lim) in checks.items():
        say(f'check {k}: {v} (limit {lim})')
    result['checks'] = {k: {'value': _finite(v), 'limit': lim}
                        for k, (v, lim) in checks.items()}
    print(json.dumps(result), file=out, flush=True)
    return result


def _finite(v):
    return v if v is None or math.isfinite(v) else None


def _devtrace():
    import devtrace
    return devtrace
