"""The driver's worker path: under a RealClock the pump runs the node's
steps on one worker thread while the event loop stays free.

Most cases drive a stub node whose ``step`` sleeps like a device wait
(the sleep releases the GIL) and then appends one token per running
request, so the loop's timing is visible without a model: SSE frames one
step apart, timers firing mid-step, node writes held until the step in
flight ends, a step's exception surfacing, and the driver's counters.
The last case serves a real tiny engine through both paths and compares
the greedy tokens.

No pytest-asyncio in the container: each test wraps its coroutine in
``asyncio.run``.
"""
import asyncio
import itertools
import json
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.clock import RealClock, VirtualClock
from repro.serving.frontend.app import FrontendApp
from repro.serving.frontend.driver import AsyncNodeDriver
from repro.serving.frontend.testing import ASGIClient
from repro.serving.scheduler import ReqState, Request

VOCAB = 1000
TIMEOUT_S = 60


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, TIMEOUT_S))


def _expected(prompt, n):
    """The stub's tokens for ``prompt``: its first id, counting up."""
    return [prompt[0] + i for i in range(n)]


class _StubEngine:
    """What the driver, the app and the batch manager use of an engine;
    every running request gains one token per node step."""

    def __init__(self, name, clock):
        self.clock = clock
        self.cfg = SimpleNamespace(max_seq=256)
        self.mcfg = SimpleNamespace(vocab_size=VOCAB, name=name)
        ids = itertools.count()
        self.session = SimpleNamespace(
            new_request_id=lambda: f'{name}-{next(ids)}')
        self.requests = {}

    def submit(self, prompt, max_new_tokens=32, req_id=None):
        rid = req_id or self.session.new_request_id()
        self.requests[rid] = Request(rid, list(prompt), max_new_tokens,
                                     state=ReqState.RUNNING,
                                     t_submit=self.clock.now())
        return rid

    def cancel(self, req_id):
        req = self.requests.get(req_id)
        if req is None or req.state is not ReqState.RUNNING:
            return False
        req.state = ReqState.CANCELLED
        return True

    def flush_tokens(self):
        pass

    def running(self):
        return [r for r in self.requests.values()
                if r.state is ReqState.RUNNING]

    def tick(self):
        for r in self.running():
            r.generated.append(r.prompt[0] + len(r.generated))
            if len(r.generated) >= r.max_new_tokens:
                r.state = ReqState.FINISHED


class _StubNode:
    """A node whose step waits ``step_s`` (on the worker, the GIL is free
    meanwhile), then ticks every engine.  It counts the steps during which
    node state changed from outside the step."""

    def __init__(self, clock, step_s, *, offline=0):
        self.clock = clock
        self.step_s = step_s
        self.online = _StubEngine('online', clock)
        self.offline = [_StubEngine(f'off{i}', clock)
                        for i in range(offline)]
        self.engines = [self.online, *self.offline]
        self.in_step = threading.Event()
        self.written_mid_step = 0
        self.step_threads = set()
        self.fail = False

    def _state(self):
        return [(rid, r.state) for e in self.engines
                for rid, r in list(e.requests.items())]

    def has_work(self):
        return any(e.running() for e in self.engines)

    def step(self):
        self.step_threads.add(threading.current_thread())
        before = self._state()
        self.in_step.set()
        time.sleep(self.step_s)
        self.in_step.clear()
        self.written_mid_step += self._state() != before
        if self.fail:
            raise RuntimeError('device lost')
        for e in self.engines:
            e.tick()
        return True


async def _until(pred, what):
    for _ in range(20000):
        if pred():
            return
        await asyncio.sleep(1e-4)
    raise AssertionError(f'never: {what}')


# ---------------------------------------------------------------------------
# The loop stays free while a step is in flight
# ---------------------------------------------------------------------------

def test_sse_frames_arrive_one_step_apart():
    """Through the ASGI app, one stream's token frames arrive about one
    30-ms step apart while the node stays busy (the in-loop turn needs
    three loop passes a frame, each behind a whole step: ~90 ms)."""
    node = _StubNode(RealClock(), 0.03)

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            busy = driver.submit_stream([1], max_new_tokens=40)
            client = ASGIClient(FrontendApp(driver))
            sr = client.stream('POST', '/v1/completions',
                               json={'prompt': [7, 8, 9], 'max_tokens': 12,
                                     'stream': True})
            stamps, toks = [], []
            async with sr:
                assert sr.status == 200
                async for ev in sr.events():
                    if ev.done:
                        break
                    c = json.loads(ev.data)['choices'][0]
                    if c.get('token') is not None:
                        stamps.append(time.monotonic())
                        toks.append(c['token'])
            await busy.collect()
            return stamps, toks, driver.stats

    stamps, toks, stats = _run(scenario())
    assert toks == _expected([7], 12)
    gap_ms = 1e3 * float(np.mean(np.diff(stamps)))
    assert gap_ms < 45, gap_ms
    assert stats.turns_off_loop == stats.ticks > 0


def test_loop_timer_fires_while_a_step_is_in_flight():
    node = _StubNode(RealClock(), 0.05)

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            stream = driver.submit_stream([3], max_new_tokens=4)
            await _until(node.in_step.is_set, 'a step in flight')
            t0 = time.monotonic()
            await asyncio.sleep(0.005)
            late = time.monotonic() - t0
            assert node.in_step.is_set()     # the same step still runs
            await stream.collect()
            return late

    assert _run(scenario()) < 0.015


# ---------------------------------------------------------------------------
# Node writes made mid-step are held until the step ends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('write', ['submit_stream', 'cancel_stream',
                                   'batch_submit', 'batch_cancel'])
def test_writes_made_mid_step_apply_after_it(write):
    node = _StubNode(RealClock(), 0.03, offline=1)
    off = node.offline[0]

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            first = driver.submit_stream([100], max_new_tokens=6)
            job = None
            if write == 'batch_cancel':
                job = driver.batches.submit([{'prompt': [500],
                                              'max_tokens': 50}])
            await _until(lambda: len(node.online.requests[
                first.req_id].generated) >= 2, 'two tokens')
            await _until(node.in_step.is_set, 'a step in flight')
            deferred0 = driver.stats.deferred
            if write == 'submit_stream':
                second = driver.submit_stream([200], max_new_tokens=5)
                held = second.req_id not in node.online.requests
            elif write == 'cancel_stream':
                assert driver.cancel_stream(first.req_id)
                held = (node.online.requests[first.req_id].state
                        is ReqState.RUNNING)
            elif write == 'batch_submit':
                job = driver.batches.submit([{'prompt': [300],
                                              'max_tokens': 3}] * 2)
                held = not off.requests
                # the job reads as queued before its items land
                assert job.to_dict()['request_counts']['queued'] == 2
            else:
                job = driver.batches.cancel(job.job_id)
                held = off.running() != []
                assert job.status == 'cancelled'
            assert held and node.in_step.is_set()
            assert driver.stats.deferred > deferred0
            out = {'first': await first.collect(),
                   'first_reason': first.finish_reason}
            if write == 'submit_stream':
                out['second'] = await second.collect()
            if job is not None:
                await _until(lambda: driver.batches.get(job.job_id).status
                             in ('completed', 'cancelled'), 'job ends')
                out['job'] = driver.batches.results(job.job_id)
            return out

    out = _run(scenario())
    assert node.written_mid_step == 0
    if write == 'cancel_stream':
        n = len(out['first'])
        assert 2 <= n < 6 and out['first_reason'] == 'cancelled'
        assert out['first'] == _expected([100], n)
    else:
        assert out['first'] == _expected([100], 6)
        assert out['first_reason'] == 'length'
    if write == 'submit_stream':
        assert out['second'] == _expected([200], 5)
    if write == 'batch_submit':
        assert [r['tokens'] for r in out['job']] == [_expected([300], 3)] * 2
    if write == 'batch_cancel':
        assert [r['status'] for r in out['job']] == ['cancelled']
        assert off.requests['off0-0'].state is ReqState.CANCELLED


def test_held_submit_keeps_its_call_time():
    """A submit held for the step in flight keeps the call's time as
    ``t_submit``, so the engine's queue wait includes that step."""
    node = _StubNode(RealClock(), 0.04)

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            driver.submit_stream([1], max_new_tokens=8)
            await _until(node.in_step.is_set, 'a step in flight')
            t_call = node.clock.now()
            second = driver.submit_stream([2], max_new_tokens=2)
            await second.collect()
            return t_call, node.online.requests[second.req_id]

    t_call, req = _run(scenario())
    assert t_call <= req.t_submit < t_call + 0.005


def test_stress_writes_against_fast_steps():
    """Many streams submitted and cancelled from the loop against 1-ms
    steps, with the interpreter switching threads every microsecond: no
    write lands inside a step, every stream gets its tokens in order, and
    every stream ends once."""
    node = _StubNode(RealClock(), 0.001)
    rng = np.random.default_rng(0)
    plan = [(int(rng.integers(1, 900)), int(rng.integers(2, 30)),
             bool(rng.random() < 0.4), float(rng.random() * 0.03))
            for _ in range(60)]

    async def one(driver, first, n, cancel, delay):
        s = driver.submit_stream([first], max_new_tokens=n)
        if cancel:
            await asyncio.sleep(delay)
            await s.cancel()
        return await s.collect(), s.finish_reason

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            outs = await asyncio.gather(*(one(driver, *p) for p in plan))
        return outs, driver.stats

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        outs, stats = _run(scenario())
    finally:
        sys.setswitchinterval(old)
    assert node.written_mid_step == 0
    for (first, n, _, _), (toks, reason) in zip(plan, outs):
        assert toks == _expected([first], len(toks))
        assert (reason, len(toks)) == ('length', n) or reason == 'cancelled'
    assert stats.streams_opened == len(plan) \
        == stats.streams_finished + stats.streams_cancelled
    assert stats.deferred > 0 and stats.turns_off_loop == stats.ticks


# ---------------------------------------------------------------------------
# Failure and shutdown; counters under either clock
# ---------------------------------------------------------------------------

def test_step_exception_surfaces_and_stop_joins_the_worker():
    node = _StubNode(RealClock(), 0.01)

    async def scenario():
        driver = AsyncNodeDriver(node)
        driver.start()
        driver.submit_stream([5], max_new_tokens=50)
        await _until(lambda: driver.stats.ticks >= 2, 'two steps')
        node.fail = True
        await _until(driver._task.done, 'the pump ends')
        with pytest.raises(RuntimeError, match='device lost'):
            await driver.stop()
        return driver

    driver = _run(scenario())
    (worker,) = node.step_threads
    assert worker is not threading.main_thread()
    assert not worker.is_alive() and driver._worker is None


@pytest.mark.parametrize('clock', ['real', 'virtual'])
def test_counters_show_which_path_ran(clock):
    real = clock == 'real'
    node = _StubNode(RealClock() if real else VirtualClock(), 0.02)

    async def scenario():
        async with AsyncNodeDriver(node) as driver:
            streams = [driver.submit_stream([10 * i], max_new_tokens=4)
                       for i in range(1, 4)]
            await _until(lambda: driver.stats.ticks > 0, 'a step')
            if real:
                await _until(node.in_step.is_set, 'a step in flight')
            streams.append(driver.submit_stream([40], max_new_tokens=4))
            outs = [await s.collect() for s in streams]
        return outs, driver.stats

    outs, stats = _run(scenario())
    assert outs == [_expected([10 * i], 4) for i in range(1, 5)]
    assert stats.ticks > 0
    if real:
        assert stats.turns_off_loop == stats.ticks and stats.deferred == 1
        assert threading.main_thread() not in node.step_threads
    else:
        assert stats.turns_off_loop == 0 and stats.deferred == 0
        assert node.step_threads == {threading.main_thread()}


# ---------------------------------------------------------------------------
# A real tiny engine: the worker path serves the same greedy tokens
# ---------------------------------------------------------------------------

def _tiny_node(clock):
    from repro.configs import get_config, reduced
    from repro.core.runtime import RuntimeConfig, ValveRuntime
    from repro.launch.node import NodeOrchestrator
    from repro.serving.engine import EngineConfig
    from repro.serving.kvpool import KVPool

    pool = KVPool(5, 4, page_size=4, reserved_handles=1)
    rt = ValveRuntime(pool, RuntimeConfig(n_devices=1, t_cool_init=0.002),
                      clock=clock)
    node = NodeOrchestrator(rt, idle_advance=1e-3)
    node.add_engine(reduced(get_config('qwen3-0.6b'), page_size=4),
                    EngineConfig(max_batch=4, max_seq=48, prefill_chunk=8,
                                 klass='online'), seed=0, name='online')
    return node


def test_worker_path_serves_the_in_loop_tokens():
    prompts = [np.random.default_rng(s).integers(1, 500, 10).tolist()
               for s in range(3)]

    async def serve(node):
        async with AsyncNodeDriver(node) as driver:
            streams = [driver.submit_stream(p, max_new_tokens=6)
                       for p in prompts]
            tokens = [await s.collect() for s in streams]
        return tokens, driver.stats

    in_loop, s_loop = _run(serve(_tiny_node(VirtualClock())))
    worker, s_worker = _run(serve(_tiny_node(RealClock())))
    assert s_loop.turns_off_loop == 0 < s_worker.turns_off_loop
    assert all(len(t) == 6 for t in in_loop)
    assert worker == in_loop
