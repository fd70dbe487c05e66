"""AsyncNodeDriver — the event loop owns the front end; one worker thread
runs the node's steps.

The serving front-end's execution model: a single asyncio task pumps
``NodeOrchestrator.step()`` while request intake and SSE writers run on
the same event loop (no thread-per-request).  Under a real clock the pump
hands each turn's node steps to the driver's one worker thread and awaits
them, so the loop stays free while the host waits on the device: an
arrival reaches ``submit`` and a token reaches the wire within the step in
flight, not several steps later.  Two threads, two kinds of state:

- only the worker runs node steps, and only while the pump awaits it;
- only the loop's thread touches asyncio objects (streams, the wake
  event), flushes stream deltas and polls batch jobs, between steps.

A write to node state from the loop (a stream's submit or cancel, a batch
job's submit or cancel) made while a step is in flight is *held*: the
pump applies it on the loop's thread right after that step, before the
next one.  Request ids are minted at the call, so a held submit still
returns its final id, and its ``t_submit`` is the call's time.  Reads
(metrics, health, batch status) may run at any time.  When the pump is
parked, writes apply at once and kick it.

Under a :class:`~repro.core.clock.VirtualClock` the pump keeps the
in-loop turn (node steps, deltas and polls on the loop, then one yield):
virtual time has no device wait to overlap, and the protocol tests rely
on its determinism.  ``drain()`` is in-loop under either clock.

Token delivery is a *tap*, not an engine hook: after each turn the driver
diffs every streamed request's ``generated`` list against what its
:class:`OnlineStream` has already emitted and pushes the deltas.  The
engine (and the Valve patch surface) stays untouched — streaming is a
front-end concern, and the ≤ 13-LOC framework patch cannot grow.

Cancellation (client disconnect, batch abort) routes to
:meth:`Engine.cancel`: the lease is released on the spot, which drops the
invalidation route with it (route lifetime == lease lifetime), so a
dropped stream can never pin reserved KV pages and starve MIAD.

Clock discipline: everything that waits goes through :func:`clock_sleep`
— under a :class:`~repro.core.clock.VirtualClock` waits *advance* the
clock instead of sleeping, so the protocol tests and the trace-replay
load generator are deterministic and never wall-clock sleep.
"""
from __future__ import annotations

import asyncio
import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

from repro.core.trace import span
from repro.launch.node import NodeOrchestrator
from repro.serving.frontend.batches import (
    _TERMINAL, BatchItem, BatchJob, BatchManager)
from repro.serving.scheduler import ReqState, Request

__all__ = ['AsyncNodeDriver', 'OnlineStream', 'TokenEvent', 'DriverStats',
           'clock_sleep']

# spans of the pump: one turn (node steps; in the loop, also stream deltas
# and batch polls), and a zero-length mark where the idle pump parks
PUMP_SPAN = 'driver.pump'
PARK_SPAN = 'driver.park'


async def clock_sleep(clock, dt: float) -> None:
    """Sleep ``dt`` on the runtime's clock: wall sleep under a RealClock,
    a pure advance (plus one loop yield) under a VirtualClock — the one
    primitive that keeps pacing/timeout tests deterministic."""
    if getattr(clock, 'virtual', False):
        if dt > 0:
            clock.advance(dt)
        await asyncio.sleep(0)
    else:
        await asyncio.sleep(max(dt, 0.0))


class TokenEvent(NamedTuple):
    """One streamed token delta (``token is None`` marks the terminal
    event carrying only the finish reason)."""
    token: Optional[int]
    index: int
    finish_reason: Optional[str]    # 'stop' | 'length' | 'cancelled'


class OnlineStream:
    """Async iterator over one online request's tokens as the engine
    produces them.  Created by :meth:`AsyncNodeDriver.submit_stream`."""

    def __init__(self, driver: 'AsyncNodeDriver', req_id: str):
        self.driver = driver
        self.req_id = req_id
        self.emitted = 0                 # tokens already pushed to the queue
        self.finish_reason: Optional[str] = None
        self._q: asyncio.Queue = asyncio.Queue()

    def __aiter__(self) -> 'OnlineStream':
        return self

    async def __anext__(self) -> TokenEvent:
        if self.finish_reason is not None and self._q.empty():
            raise StopAsyncIteration
        ev: TokenEvent = await self._q.get()
        if ev.finish_reason is not None:
            self.finish_reason = ev.finish_reason
            if ev.token is None:
                raise StopAsyncIteration
        return ev

    async def cancel(self) -> bool:
        """Abandon this stream's request (idempotent)."""
        return self.driver.cancel_stream(self.req_id)

    async def collect(self) -> List[int]:
        """Drain the stream to completion; returns all generated tokens."""
        return [ev.token async for ev in self if ev.token is not None]


@dataclass
class DriverStats:
    ticks: int = 0                   # node steps pumped
    streams_opened: int = 0
    streams_finished: int = 0
    streams_cancelled: int = 0
    idle_parks: int = 0              # pump waits for a kick
    turns_off_loop: int = 0          # turns whose steps ran on the worker
    deferred: int = 0                # node writes held for a step in flight


class _HeldItem(BatchItem):
    """A batch item whose engine submit may still be held for the step in
    flight; until it lands the item reads as a queued request."""

    @property
    def request(self) -> Request:
        return (self.engine.requests.get(self.req_id)
                or Request(self.req_id, self.prompt, self.max_new_tokens))


class _DriverBatches(BatchManager):
    """The driver's batch jobs: the engine writes of a submit or a cancel
    go through :meth:`AsyncNodeDriver._write`, so one made while a step is
    in flight is held until that step ends.  Ids are minted and a job's
    status settles at the call, so a job reads the same either way."""

    def __init__(self, driver: 'AsyncNodeDriver'):
        super().__init__(driver.node)
        self._write = driver._write

    def submit(self, requests: Sequence[dict]) -> BatchJob:
        offline = self.node.offline
        assert offline, 'node has no offline engines'
        assert requests, 'empty batch'
        items: List[BatchItem] = []
        for i, spec in enumerate(requests):
            prompt = list(map(int, spec['prompt']))
            max_new = int(spec.get('max_tokens', 16))
            eng = offline[self._rr % len(offline)]
            self._rr += 1
            assert len(prompt) + max_new <= eng.cfg.max_seq, \
                (len(prompt), max_new, eng.cfg.max_seq)
            rid = eng.session.new_request_id()
            self._write(functools.partial(eng.submit, prompt, max_new, rid))
            items.append(_HeldItem(i, prompt, max_new, req_id=rid,
                                   engine=eng))
        job = BatchJob(f'batch-{next(self._seq)}', items,
                       created_at=self.node.clock.now())
        self.jobs[job.job_id] = job
        return job

    def cancel(self, job_id: str) -> Optional[BatchJob]:
        job = self.jobs.get(job_id)
        if job is not None and job.status not in _TERMINAL:
            for it in job.items:
                self._write(functools.partial(it.engine.cancel, it.req_id))
            job.status = 'cancelled'
            job.completed_at = self.node.clock.now()
        return job


class AsyncNodeDriver:
    """Pumps one :class:`NodeOrchestrator` from the event loop (its steps
    on one worker thread under a real clock) and exposes async submission
    surfaces (online streams + batch jobs)."""

    def __init__(self, node: NodeOrchestrator, *,
                 ticks_per_yield: int = 1):
        self.node = node
        self.clock = node.clock
        self.stats = DriverStats()
        # ≥1 node steps per turn: raising this lengthens each turn, after
        # which stream deltas, batch polls and held writes are applied
        # (the ``driver.pump`` spans time the turns)
        self.ticks_per_yield = max(1, int(ticks_per_yield))
        self._streams: Dict[str, OnlineStream] = {}
        self._wake = asyncio.Event()
        self._task: Optional[asyncio.Task] = None
        self._stopping = False
        # the worker that runs the node's steps (None: in-loop turns), and
        # the writes held while a step is in flight on it
        self._worker: Optional[ThreadPoolExecutor] = None
        self._in_flight = False
        self._held: List[Callable[[], object]] = []
        self.batches = _DriverBatches(self)

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    async def __aenter__(self) -> 'AsyncNodeDriver':
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    def start(self) -> None:
        """Start the pump task (must run inside the owning event loop);
        under a real clock, also the worker thread for the node's steps."""
        assert self._task is None, 'driver already started'
        self._stopping = False
        self._in_flight = False
        if not getattr(self.clock, 'virtual', False):
            self._worker = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix='node-step')
        self._task = asyncio.get_running_loop().create_task(self._pump())

    async def stop(self) -> None:
        """Stop the pump (idempotent): wait for the step in flight, then
        shut the worker down.  In-flight requests stay in the engines; a
        restarted driver resumes them.  A step's exception re-raises."""
        self._stopping = True
        self._wake.set()
        try:
            if self._task is not None:
                await self._task
                self._task = None
        finally:
            if self._worker is not None:
                self._worker.shutdown(wait=True)
                self._worker = None

    def kick(self) -> None:
        """Wake an idle pump (new work arrived)."""
        self._wake.set()

    def _write(self, apply: Callable[[], object]) -> None:
        """Apply a write to node state now, or hold it until the step in
        flight ends (the pump applies held writes in order)."""
        if self._in_flight:
            self._held.append(apply)
            self.stats.deferred += 1
        else:
            apply()

    # ------------------------------------------------------------------
    # Online streaming surface
    # ------------------------------------------------------------------
    def submit_stream(self, prompt: Sequence[int],
                      max_new_tokens: int = 32) -> OnlineStream:
        """Submit one online request; returns its token stream.  The id is
        final at once; the engine submit may be held for the step in
        flight, and ``t_submit`` is this call's time either way."""
        eng = self.node.online
        assert eng is not None, 'node has no online engine'
        prompt, t = list(prompt), self.clock.now()
        assert prompt and len(prompt) + max_new_tokens <= eng.cfg.max_seq, \
            (len(prompt), max_new_tokens, eng.cfg.max_seq)
        rid = eng.session.new_request_id()

        def apply():
            eng.submit(prompt, max_new_tokens, req_id=rid)
            eng.requests[rid].t_submit = t
        stream = OnlineStream(self, rid)
        self._streams[rid] = stream
        self.stats.streams_opened += 1
        self._write(apply)
        self.kick()
        return stream

    def _engine_holding(self, req_id: str):
        """Resolve which engine holds ``req_id`` right now.  On a plain
        node that is ``node.online``; nodes/planes that move requests
        between engines (cross-pool rescue, disaggregated prefill→decode
        handoff) expose ``engine_of`` and the driver follows the request
        wherever it lives."""
        finder = getattr(self.node, 'engine_of', None)
        eng = finder(req_id) if finder is not None else None
        return eng if eng is not None else self.node.online

    def cancel_stream(self, req_id: str) -> bool:
        """Cancel an online request (client disconnect path): the holding
        engine releases its lease immediately — on whichever pool the
        request sits, including mid-handoff — and the stream gets a
        terminal ``cancelled`` event.  While a step is in flight the
        cancel is held until it ends, and this returns whether the driver
        still streams the request (it may yet finish in that step)."""
        if self._in_flight:
            self._write(functools.partial(self._cancel, req_id))
            return req_id in self._streams
        return self._cancel(req_id)

    def _cancel(self, req_id: str) -> bool:
        eng = self._engine_holding(req_id)
        cancelled = eng is not None and eng.cancel(req_id)
        if cancelled:
            self.stats.streams_cancelled += 1
        self._flush_streams()
        return cancelled

    def _flush_streams(self) -> None:
        """Diff streamed requests against emitted counts; push deltas and
        terminal events.  Requests may live on different engines (a
        disaggregated handoff moves them mid-stream); each holding engine
        flushes its fused-path lazy tokens once per pass."""
        if not self._streams:
            return
        flushed: set = set()
        done: List[str] = []
        for rid, stream in self._streams.items():
            eng = self._engine_holding(rid)
            if id(eng) not in flushed:
                flushed.add(id(eng))
                eng.flush_tokens()   # resolve fused-path lazy tokens
            req = eng.requests.get(rid)
            if req is None:
                continue            # its submit is held for the step
            while stream.emitted < len(req.generated):
                stream._q.put_nowait(TokenEvent(
                    req.generated[stream.emitted], stream.emitted, None))
                stream.emitted += 1
            if req.state is ReqState.FINISHED:
                reason = ('length'
                          if len(req.generated) >= req.max_new_tokens
                          else 'stop')
                stream._q.put_nowait(TokenEvent(None, stream.emitted, reason))
                self.stats.streams_finished += 1
                done.append(rid)
            elif req.state is ReqState.CANCELLED:
                stream._q.put_nowait(
                    TokenEvent(None, stream.emitted, 'cancelled'))
                done.append(rid)
        for rid in done:
            del self._streams[rid]

    # ------------------------------------------------------------------
    # The pump
    # ------------------------------------------------------------------
    def _has_work(self) -> bool:
        return self.node.has_work()

    async def _pump(self) -> None:
        loop = asyncio.get_running_loop()
        while not self._stopping:
            if not self._has_work():
                self._flush_streams()
                self._wake.clear()
                if self._has_work() or self._stopping:
                    continue        # a submit raced the clear (same task
                                    # can't, but a re-kick costs nothing)
                self.stats.idle_parks += 1
                with span(PARK_SPAN):
                    pass
                await self._wake.wait()
                continue
            if self._worker is None:
                self._turn()
                # hand the loop to intake / SSE writers between dispatches
                await asyncio.sleep(0)
                continue
            # the loop runs intake and SSE writers while the worker steps;
            # node writes made meanwhile are held (``_write``)
            self._in_flight = True
            await loop.run_in_executor(self._worker, self._worker_turn)
            self.stats.turns_off_loop += 1
            self._flush_streams()
            self.batches.poll()
            self._in_flight = False
            held, self._held = self._held, []
            for apply in held:
                apply()

    def _steps(self) -> None:
        for _ in range(self.ticks_per_yield):
            if not self._has_work():
                break
            self.node.step()
            self.stats.ticks += 1

    def _worker_turn(self) -> None:
        """One pump turn on the worker thread: the node's steps alone."""
        with span(PUMP_SPAN):
            self._steps()

    def _turn(self) -> None:
        """One pump turn, which holds the event loop: up to
        ``ticks_per_yield`` node steps, then stream deltas and batch polls."""
        with span(PUMP_SPAN):
            self._steps()
            self._flush_streams()
            self.batches.poll()

    async def drain(self, max_ticks: int = 100_000) -> None:
        """Pump until the node is idle WITHOUT a running pump task (test
        and benchmark convenience; mirrors ``NodeOrchestrator.drain``)."""
        assert self._task is None, 'drain() conflicts with a running pump'
        for _ in range(max_ticks):
            if not self._has_work():
                self._flush_streams()
                self.batches.poll()
                return
            self._turn()
            await asyncio.sleep(0)
        raise RuntimeError('drain exceeded max_ticks')
