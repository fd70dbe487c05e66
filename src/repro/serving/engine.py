"""Continuous-batching inference engine with the Valve patch surface.

The execution layer of the serving plane.  Scheduling policy lives in
:mod:`repro.serving.scheduler` (:class:`BatchScheduler` composes each
dispatch: budgeted multi-request chunked prefill + piggybacked decode
slots); this module turns a :class:`ScheduledBatch` into one fixed-shape
JAX dispatch over preallocated host buffers, so each entry point compiles
once and no step reallocates numpy arrays.

Valve integration points (and *only* these — Table 1's deployability claim):
the engine holds ONE class-scoped :class:`~repro.core.api.ValveSession`
(``runtime.open_session``), whose calls — admit/finish bundles, iteration
notifications, the gate check — are tagged ``# VALVE-SESSION`` and counted
by ``tests/test_patch_surface.py`` alongside the ≤ 13-LOC invalidation
patch (:meth:`Engine.on_pages_invalidated`).  The session owns invalidation
routing by allocation ownership, so there is no per-request bind/unbind
and no engine-instance id discriminator anymore.

Memory-plane API v1: ``session.admit`` returns a
:class:`~repro.core.memory.KVLease` (list-compatible with the old page
list).  The engine passes each request's prompt so page-aligned shared
prefixes attach copy-on-write (prefill skips them — the scheduler reads
``lease.resume_tokens``), reports fill progress via ``lease.note_filled``
(which publishes prefix pages for later admissions), and the invalidation
patch resumes recompute from the surviving prefix the
:class:`~repro.core.memory.LeaseInvalidation` carries instead of
restarting at token 0.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import PoolSession
from repro.core.clock import RealClock
from repro.core.trace import span
from repro.kernels.paged_attention.prefix import build_shared_runs
from repro.serving.kvpool import QUARANTINE_PAGE
from repro.serving.sampler import sample
from repro.serving.scheduler import (
    BatchScheduler, DecodeSlot, Request, ReqState, ScheduledBatch,
    SchedulerConfig)

# re-exported for compatibility: request bookkeeping moved to scheduler.py
__all__ = ['Engine', 'EngineConfig', 'EngineStats', 'Request', 'ReqState']

# the phases of a step, each a span ``engine.step.<phase>:<engine name>``:
# batch composition and admission, filling and staging the host buffers,
# the jitted call, the host's wait for the sampled tokens, and appending
# them to the requests
PHASES = ('schedule', 'stage', 'launch', 'sync', 'commit')

# jaxlib 0.4.3x CPU async dispatch intermittently corrupts the fused
# lazy-token chain (sampled tokens feeding the next dispatch on-device with
# no host sync in between) when host-side scheduling runs concurrently with
# an executing dispatch.  The flag is read once, when the CPU client is
# created, so it must be set at import time — any realistic flow imports
# this module before touching jax.  ``Engine._dispatch_decode`` additionally
# blocks on each fused step's tokens as a backstop for processes whose
# client predates this import.  TPU/GPU are unaffected by either.
try:
    jax.config.update('jax_cpu_enable_async_dispatch', False)
except AttributeError:          # flag absent on this jax version
    pass


@dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 512              # prompt + generation budget per request
    prefill_chunk: int = 64         # per-request prefill tokens per dispatch
    max_prefill_reqs: int = 4       # prefill rows per mixed dispatch
    # total prefill tokens per dispatch; None → max_prefill_reqs × chunk
    prefill_budget: Optional[int] = None
    piggyback_decode: bool = True   # decode slots ride along with prefill
    temperature: float = 0.0
    seed: int = 0
    klass: str = 'offline'          # 'online' | 'offline'
    eos_token: Optional[int] = None
    # Decode attention through the Pallas paged kernel (pages stream
    # HBM→VMEM via the page table) instead of the full-gather oracle.
    # None → auto: kernel on TPU, oracle elsewhere (the interpreter would
    # only slow CPU runs down; parity is covered by the kernel test suite).
    decode_kernel: Optional[bool] = None
    # Fused decode+sampling fast path: the decode dispatch returns sampled
    # (B,) tokens instead of (B, V) logits (fused unembed+argmax — logits
    # never round-trip to HBM), tokens stay on device between decode
    # iterations (no per-step host sync; values are fetched lazily for
    # stream emission via Engine.flush_tokens / output_tokens), and the KV
    # cache is donated to the jitted step on accelerator backends.  Greedy
    # drain output is bit-identical to the unfused path.  With eos_token
    # set, tokens are fetched every step (the stop check needs the value).
    fused_sampling: bool = False
    # Deduplicate copy-on-write shared prefix pages across each decode
    # batch (kernels.paged_attention.prefix): each shared physical page is
    # read once per batch instead of once per request.
    prefix_shared_attention: bool = False
    # Tensor-parallel serving: a jax.sharding.Mesh to run every dispatch
    # across.  Params/cache shard by SERVE_RULES (heads/kv_heads/ffn/vocab
    # over 'model', batch over 'data'; the KV page axis stays unsharded so
    # the pool's handle space is mesh-global), resolved shape-aware so
    # indivisible dims relocate instead of failing.  None — the default —
    # is the identity single-device path: drain output is bit-identical.
    # With a mesh, decode_kernel=None resolves to the oracle path (GSPMD
    # partitions the jnp attention; the Pallas kernel is opted into
    # explicitly where the backend supports sharded custom calls).
    mesh: Optional[object] = None

    def scheduler_config(self) -> SchedulerConfig:
        return SchedulerConfig(
            max_batch=self.max_batch, chunk=self.prefill_chunk,
            max_prefill_reqs=min(self.max_prefill_reqs, self.max_batch),
            prefill_budget=self.prefill_budget,
            piggyback_decode=self.piggyback_decode)


@dataclass
class EngineStats:
    steps: int = 0
    dispatches: int = 0             # actual device dispatches issued
    mixed_dispatches: int = 0       # dispatches carrying ≥1 prefill slot
    prefill_chunks: int = 0         # prefill slots executed (per-request)
    decode_iterations: int = 0      # dispatches carrying ≥1 decode slot
    tokens_generated: int = 0
    tokens_recomputed: int = 0
    invalidations: int = 0
    blocked_dispatches: int = 0     # offline dispatches skipped while gated
    spills: int = 0                 # surviving prefixes dropped under pressure
    cancellations: int = 0          # requests abandoned before finishing
    token_flushes: int = 0          # lazy device→host token syncs (fused path)
    shared_page_reads_saved: int = 0  # page reads deduped by prefix sharing
    # time to first token split at the engine, added once per request:
    # Σ (launch of its first dispatch − submit) over ``queued`` requests,
    # and Σ (first token − that launch) over ``prefilled`` requests
    queue_wait_s: float = 0.0
    queued: int = 0
    prefill_s: float = 0.0
    prefilled: int = 0


class Engine:
    """One engine = one model instance on one node's devices."""

    def __init__(self, model, params, pool,
                 cfg: Optional[EngineConfig] = None, *,
                 runtime=None, clock=None):
        self.model = model
        self.mcfg = model.cfg
        self.cfg = cfg or EngineConfig()
        self.params = params
        self.runtime = runtime
        # with a runtime, the node-shared pool is authoritative; passing a
        # DIFFERENT pool alongside it would silently serve divergent state
        assert runtime is None or pool is None or pool is runtime.pool, \
            'pool conflicts with runtime.pool'
        self.pool = runtime.pool if runtime is not None else pool
        assert self.pool is not None, 'engine needs a KVPool or a runtime'
        self.clock = clock or (runtime.clock if runtime else RealClock())
        # the complete Valve control-plane integration: one class-scoped
        # session (alloc/notify/gate/invalidation-routing); a bare pool
        # gets the same interface with no runtime behind it
        if runtime is not None:
            self.session = runtime.open_session(                # VALVE-SESSION
                self.cfg.klass, on_invalidate=self.on_pages_invalidated)
        else:
            self.session = PoolSession(self.pool, self.cfg.klass)
        # tensor-parallel plane: commit params and KV cache to their
        # SERVE_RULES shardings up front so every dispatch compiles against
        # stable shardings (no per-call input resharding / signature churn).
        # The cache is generated straight into its sharding; params built
        # that way (NodeOrchestrator.add_engine) make the put a no-op.
        self.mesh = self.cfg.mesh
        self._c_sharding = None
        if self.mesh is None:
            self.cache = model.init_cache(None, engine_pages=self.pool.n_pages)
        else:
            p_sh, self._c_sharding = model.serve_shardings(
                self.mesh, self.pool.n_pages)
            self.params = jax.device_put(self.params, p_sh)
            self.cache = model.init_cache(None, engine_pages=self.pool.n_pages,
                                          shardings=self._c_sharding)
        self.pg = self.mcfg.page_size
        self.maxp = self.cfg.max_seq // self.pg
        self.requests: Dict[str, Request] = {}
        self.sched = BatchScheduler(self.cfg.scheduler_config())
        # the scheduler owns the lists; the engine (and the Valve patch)
        # aliases them — same objects, never rebound
        self.queue: List[str] = self.sched.queue
        self.running: List[str] = self.sched.running
        self.stats = EngineStats()
        self.rename(f'{self.cfg.klass}:{self.mcfg.name}')
        self._key = jax.random.PRNGKey(self.cfg.seed)
        assert self.mcfg.family in ('dense', 'vlm', 'moe'), \
            'engine serves paged-KV decoder-only families'
        decode_kernel = self.cfg.decode_kernel
        if decode_kernel is None:
            decode_kernel = (jax.default_backend() == 'tpu'
                             and self.mesh is None)
        # the resolved decode attention path (True = Pallas paged kernel)
        self.decode_kernel = decode_kernel
        # donate the KV cache buffers to the jitted step so the pools
        # update in place (donation is a no-op on CPU and would only warn)
        donate = (1,) if jax.default_backend() in ('tpu', 'gpu') else ()
        # mesh path: trace under the SERVE_RULES context so the models'
        # `constrain` calls become real sharding constraints, and pin the
        # cache's output sharding to its input sharding so the carried
        # cache never drifts (drift would re-specialize the jit signature
        # every step)
        if self.mesh is not None:
            from repro.distributed.sharding import SERVE_RULES, axis_rules
            mesh = self.mesh

            def _traced(fn):
                def wrapped(*args):
                    with axis_rules(mesh, SERVE_RULES):
                        return fn(*args)
                return wrapped
            jit_kw = {'out_shardings': (self._c_sharding, None)}
        else:
            def _traced(fn):
                return fn
            jit_kw = {}
        self._decode = jax.jit(
            _traced(lambda p, c, b, k=decode_kernel: model.decode_fn(
                p, c, b, use_pallas=k)),
            donate_argnums=donate, **jit_kw)
        if self.cfg.fused_sampling:
            temp = float(self.cfg.temperature)

            def fused_fn(p, c, b, k=decode_kernel, t=temp):
                # next-token feed: rows whose last sampled token is still
                # on device read it straight from the previous dispatch's
                # output instead of a host-staged value
                db = dict(b)
                db['tokens'] = jnp.where(db.pop('use_prev') > 0,
                                         db.pop('prev')[db.pop('src')],
                                         db['tokens'])
                return model.decode_sample_fn(p, c, db, use_pallas=k,
                                              temperature=t)
            self._fused_decode = jax.jit(_traced(fused_fn),
                                         donate_argnums=donate, **jit_kw)
            # see the module-import async-dispatch note at the top of this
            # file; the per-step block below is the backstop for processes
            # whose CPU client predates that config update
            self._cpu_step_sync = jax.default_backend() == 'cpu'
        chunk_fn = model.mod.prefill_chunk
        self._mixed = jax.jit(
            _traced(lambda p, c, b: chunk_fn(self.mcfg, p, c, b)), **jit_kw)
        self._init_buffers()
        # lazy-token bookkeeping (fused path): device arrays whose values
        # have not been copied to req.generated yet, and the row map of
        # the newest decode output (the device-feed source)
        self._pending: List[tuple] = []
        # staged-device-array cache for decode dispatch inputs (see
        # _dispatch_decode): keyed by the exact host bytes they derive from
        self._stage: Dict = {}
        self._pending_rids: set = set()
        self._prev_tokens = jnp.zeros((self.cfg.max_batch,), jnp.int32)
        self._prev_rows: Dict[str, int] = {}
        self._seed_ctr = itertools.count()

    def rename(self, name: str) -> None:
        """Name the engine (a node gives it its key in ``names``) and
        build the names of its step-phase spans."""
        self.name = name
        self._spans = {p: f'engine.step.{p}:{name}' for p in PHASES}

    def _init_buffers(self) -> None:
        """Preallocate the fixed-shape host staging buffers (one mixed
        dispatch shape, one decode dispatch shape) — filled in place each
        step, never reallocated."""
        b, c = self.cfg.max_batch, self.cfg.prefill_chunk
        self._mix = {
            'toks': np.zeros((b, c), np.int32),
            'poss': np.zeros((b, c), np.int32),
            'pids': np.zeros((b, c), np.int32),
            'offs': np.zeros((b, c), np.int32),
            'pts': np.zeros((b, self.maxp), np.int32),
            'kv_len': np.zeros((b,), np.int32),
            'last_idx': np.zeros((b,), np.int32),
        }
        self._dec = {
            'toks': np.zeros((b,), np.int32),
            'poss': np.zeros((b,), np.int32),
            'pts': np.zeros((b, self.maxp), np.int32),
            # fused path: per-row device-feed selectors (see fused_fn)
            'use_prev': np.zeros((b,), np.int32),
            'src': np.zeros((b,), np.int32),
        }

    # ------------------------------------------------------------------
    # Submission
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               req_id: Optional[str] = None) -> str:
        # no bind step: invalidation routing follows allocation ownership
        # (the session records it at admit, releases it at finish/reclaim)
        rid = req_id or self.session.new_request_id()       # VALVE-SESSION
        assert len(prompt) > 0, 'empty prompt'
        assert len(prompt) + max_new_tokens <= self.cfg.max_seq, \
            (len(prompt), max_new_tokens, self.cfg.max_seq)
        req = Request(rid, list(map(int, prompt)), max_new_tokens,
                      t_submit=self.clock.now())
        self.requests[rid] = req
        self.sched.submit(rid)
        return rid

    # ------------------------------------------------------------------
    # Valve patch surface — the complete framework-side modification.
    # LOC counted by tests/test_patch_surface.py (paper Table 1: < 20).
    # ------------------------------------------------------------------
    # >>> VALVE-PATCH-BEGIN
    def on_pages_invalidated(self, invalidated: Dict[str, List[int]]) -> None:
        for rid, inv in invalidated.items():
            # session routing delivers only ids holding a live lease, so
            # the request exists and is not FINISHED
            req = self.requests[rid]
            # recompute charge: a queued victim hit again loses only the
            # shrink from its old resume point (0 for duplicate deliveries)
            base = req.n_prefilled if rid in self.queue else len(req.context)
            self.stats.tokens_recomputed += base - inv.resume
            # keep the surviving prefix: prefill resumes at inv.resume
            req.pages, req.n_prefilled = req.pages[:inv.keep], inv.resume
            if rid in self.queue:
                continue
            req.state, req.recomputes = ReqState.WAITING, req.recomputes + 1
            self.running.remove(rid)
            self.queue.insert(0, rid)
            self.stats.invalidations += 1
    # >>> VALVE-PATCH-END

    # ------------------------------------------------------------------
    # Memory plumbing
    # ------------------------------------------------------------------
    def _fill_page_table(self, row: np.ndarray, req: Request) -> np.ndarray:
        row.fill(QUARANTINE_PAGE)
        row[: len(req.pages)] = req.pages
        return row

    # ------------------------------------------------------------------
    # Scheduling step
    # ------------------------------------------------------------------
    def _gated(self) -> bool:
        return not self.session.may_dispatch()              # VALVE-SESSION

    def _try_admit(self, req: Request) -> Optional[List[int]]:
        """Admission callback for the scheduler.  The session bundles the
        lifecycle notification with the lease — lifecycle first, so the
        request's arrival closes the gates BEFORE any allocation can
        trigger reclamation (one preemption covers both).  Passing the
        prompt opts into copy-on-write prefix sharing: an already-
        materialized page-aligned prefix is attached instead of recomputed
        (``lease.resume_tokens`` tells the scheduler where prefill starts);
        re-admitting a partially-invalidated request extends its live lease
        and keeps the surviving prefix."""
        need = -(-req.target_len // self.pg)
        lease = self.session.admit(                         # VALVE-SESSION
            req.req_id, need, req.prompt)
        if lease is not None:
            # None must NOT clobber req.lease: a failed RE-admission leaves
            # the surviving lease live in the plane, and _spill needs the
            # handle to actually release it
            req.lease = lease
        return lease

    def _spill(self, req: Request) -> None:
        """Scheduler deadlock valve: drop a waiting request's surviving-
        prefix pages under sustained admission pressure (degrades to the
        legacy whole-request recompute)."""
        if req.lease is not None:
            req.lease.release()
        # the forfeited surviving prefix becomes recompute work
        self.stats.tokens_recomputed += req.n_prefilled
        req.pages, req.n_prefilled, req.lease = [], 0, None
        self.stats.spills += 1

    def _finish(self, req: Request) -> None:
        req.state = ReqState.FINISHED
        self.running.remove(req.req_id)
        self.session.finish(req.req_id)                     # VALVE-SESSION
        req.pages, req.lease = [], None

    # ------------------------------------------------------------------
    # Cancellation (client disconnect / batch-job abort)
    # ------------------------------------------------------------------
    def cancel(self, req_id: str) -> bool:
        """Abandon a submitted request; returns False if unknown/terminal.

        A RUNNING/PREFILL request goes through the normal terminal bundle
        (``session.finish``: lease + route + lifecycle end — for online
        requests the lifecycle start fired at admission, so the pairing
        stays balanced).  A QUEUED request was never admitted, so there is
        no lifecycle notification to unwind; its only possible KV is a
        surviving prefix kept across an invalidation, and releasing the
        lease drops the route with it (route lifetime == lease lifetime).
        A dropped stream therefore can never pin reserved pages."""
        req = self.requests.get(req_id)
        if req is None or req.state in (ReqState.FINISHED,
                                        ReqState.CANCELLED):
            return False
        if req_id in self.queue:
            self.queue.remove(req_id)
            if req.lease is not None and not req.lease.released:
                req.lease.release()
            req.pages, req.lease = [], None
        else:
            self._finish(req)
        req.state = ReqState.CANCELLED
        self.stats.cancellations += 1
        return True

    # -- mixed prefill(+decode) dispatch -------------------------------------
    def _dispatch_mixed(self, batch: ScheduledBatch) -> None:
        """Execute one composed dispatch through the chunked-prefill entry:
        prefill rows write/attend their chunk; decode rows are one-token
        chunks (embed the last sampled token, write its KV, predict the
        next) — one fixed (max_batch × chunk) iteration for all of it."""
        # prefill rows (and piggybacked decode rows) re-read context token
        # VALUES, so lazily-held device tokens must land first; the
        # newest-output row map dies with this dispatch (rows resample)
        self.flush_tokens()
        self._prev_rows = {}
        with span(self._spans['stage']):
            mb = self._stage_mixed(batch)
        self.session.iteration_start()                      # VALVE-SESSION
        with span(self._spans['launch'],
                  rows=len(batch.prefill) + len(batch.decode),
                  prefill=' '.join(ps.req_id for ps in batch.prefill)):
            self._count_queue_wait(batch.prefill)
            self.cache, logits = self._mixed(self.params, self.cache, mb)
        self.session.iteration_end()                        # VALVE-SESSION
        with span(self._spans['sync']):
            new = np.asarray(self._sample(logits))
        with span(self._spans['commit']):
            self._commit_mixed(batch, new)

    def _stage_mixed(self, batch: ScheduledBatch) -> dict:
        """Fill the mixed dispatch's host buffers and stage them."""
        m = self._mix
        m['toks'].fill(0)
        m['poss'].fill(0)
        m['pids'].fill(QUARANTINE_PAGE)
        m['offs'].fill(0)
        m['pts'].fill(QUARANTINE_PAGE)
        m['kv_len'].fill(1)        # padding rows attend 1 quarantine slot
        m['last_idx'].fill(0)
        row = 0
        for ps in batch.prefill:
            req = self.requests[ps.req_id]
            lo, hi = ps.start, ps.start + ps.length
            pos = np.arange(lo, hi)
            m['toks'][row, :ps.length] = req.context[lo:hi]
            m['poss'][row, :ps.length] = pos
            m['poss'][row, ps.length:] = hi - 1
            pt = self._fill_page_table(m['pts'][row], req)
            m['pids'][row, :ps.length] = pt[pos // self.pg]
            m['offs'][row, :ps.length] = pos % self.pg
            m['kv_len'][row] = hi
            m['last_idx'][row] = ps.length - 1
            row += 1
        for ds in batch.decode:
            req = self.requests[ds.req_id]
            # the last context token was sampled but its KV never written:
            # this row embeds it, writes KV at its position, predicts next
            pos = len(req.context) - 1
            m['toks'][row, 0] = req.context[-1]
            m['poss'][row, :] = pos
            pt = self._fill_page_table(m['pts'][row], req)
            m['pids'][row, 0] = pt[pos // self.pg]
            m['offs'][row, 0] = pos % self.pg
            m['kv_len'][row] = pos + 1
            m['last_idx'][row] = 0
            row += 1
        return {
            'tokens': jnp.asarray(m['toks']),
            'positions': jnp.asarray(m['poss']),
            'page_table': jnp.asarray(m['pts']),
            'page_ids': jnp.asarray(m['pids']),
            'offsets': jnp.asarray(m['offs']),
            'kv_len': jnp.asarray(m['kv_len']),
            'last_idx': jnp.asarray(m['last_idx']),
        }

    def _count_queue_wait(self, prefill) -> None:
        """At the launch of the first dispatch carrying a request (a
        re-admission after an invalidation is not counted again)."""
        now = self.clock.now()
        for ps in prefill:
            req = self.requests[ps.req_id]
            if req.t_first_dispatch is None:
                req.t_first_dispatch = now
                self.stats.queue_wait_s += now - req.t_submit
                self.stats.queued += 1

    def _commit_mixed(self, batch: ScheduledBatch, new: np.ndarray) -> None:
        """Record a mixed dispatch's sampled tokens and fill progress."""
        self.stats.dispatches += 1
        self.stats.mixed_dispatches += 1
        self.stats.prefill_chunks += len(batch.prefill)
        if batch.decode:
            self.stats.decode_iterations += 1
        row = 0
        for ps in batch.prefill:
            req = self.requests[ps.req_id]
            req.n_prefilled = ps.start + ps.length
            if req.lease is not None:   # fill fact → prefix publication
                req.lease.note_filled(req.n_prefilled)
            if req.n_prefilled == len(req.context):
                req.state = ReqState.RUNNING
                # the final chunk's logits predict the token after the
                # context — the first token on a fresh prefill, the resume
                # token after an invalidation recompute
                self._append_token(req, int(new[row]))
            row += 1
        for ds in batch.decode:
            req = self.requests[ds.req_id]
            req.decode_steps += 1
            self._append_token(req, int(new[row]))
            row += 1

    # -- pure decode dispatch -------------------------------------------------
    def _dispatch_decode(self, slots: List[DecodeSlot]) -> None:
        """Decode-only iteration through the paged-attention fast path.

        With ``fused_sampling`` the dispatch returns sampled tokens, not
        logits: each row's next-token input is read on-device from the
        previous dispatch's output (``use_prev``/``src`` feed), and the
        new tokens are recorded as placeholders resolved lazily by
        :meth:`flush_tokens` — the per-step device→host sync is gone."""
        fused = self.cfg.fused_sampling
        if fused and any(ds.req_id in self._pending_rids
                         and ds.req_id not in self._prev_rows
                         for ds in slots):
            # a slot's pending token predates the newest device array (the
            # request sat out a step): resolve to host values once
            self.flush_tokens()
        with span(self._spans['stage']):
            db = self._stage_decode(slots)
        self.session.iteration_start()                      # VALVE-SESSION
        with span(self._spans['launch'], rows=len(slots)):
            if fused:
                self.cache, toks = self._fused_decode(self.params, self.cache,
                                                      db)
            else:
                self.cache, logits = self._decode(self.params, self.cache, db)
        self.session.iteration_end()                        # VALVE-SESSION
        self.stats.dispatches += 1
        self.stats.decode_iterations += 1
        if not fused:
            with span(self._spans['sync']):
                new = np.asarray(self._sample(logits))
            with span(self._spans['commit']):
                for i, ds in enumerate(slots):
                    req = self.requests[ds.req_id]
                    req.decode_steps += 1
                    self._append_token(req, int(new[i]))
            return
        if self._cpu_step_sync:
            with span(self._spans['sync']):
                jax.block_until_ready(toks)  # see module header: dispatch race
        with span(self._spans['commit']):
            self._commit_fused(slots, toks)

    def _stage_decode(self, slots: List[DecodeSlot]) -> dict:
        """Fill the decode dispatch's host buffers and stage them."""
        fused = self.cfg.fused_sampling
        d = self._dec
        d['toks'].fill(0)
        d['poss'].fill(0)
        d['pts'].fill(QUARANTINE_PAGE)
        d['use_prev'].fill(0)
        d['src'].fill(0)
        for i, ds in enumerate(slots):
            req = self.requests[ds.req_id]
            if fused and ds.req_id in self._pending_rids:
                d['use_prev'][i] = 1
                d['src'][i] = self._prev_rows[ds.req_id]
            else:
                d['toks'][i] = req.context[-1]
            d['poss'][i] = len(req.context) - 1
            self._fill_page_table(d['pts'][i], req)
        # padded slots write into quarantine (page 0) — harmless by design.
        # Staging cache: the page tables — and the shared-run structure
        # derived from (tables, length//pg) — only change when a page is
        # appended, remapped, or the batch recomposes, so the staged device
        # arrays are reused between changes (host→device staging and the
        # shared-run rebuild otherwise dominate CPU step latency).
        st = self._stage
        key = (d['pts'].tobytes(), ((d['poss'] + 1) // self.pg).tobytes())
        if st.get('key') != key:
            st['key'] = key
            st['pts'] = jnp.asarray(d['pts'])
            st['shared'] = None
            if self.cfg.prefix_shared_attention:
                runs = build_shared_runs(d['pts'], d['poss'] + 1, self.pg)
                if runs['n_slots']:
                    # each shared physical page is read once per batch; the
                    # saving is (participants − 1) reads per slot
                    st['saved'] = int(runs['mask'].sum()) - runs['n_slots']
                    # bucket the slot axis to the next power of two: the
                    # full maxp-wide padding would double the shared-phase
                    # FLOPs; a few buckets cost a few compiles.  The tail
                    # axis stays maxp-wide on purpose — its live width
                    # grows every page crossing, so bucketing it would
                    # recompile the dispatch mid-decode
                    cap = 1
                    while cap < runs['n_slots']:
                        cap <<= 1
                    st['shared'] = {
                        'pages': jnp.asarray(runs['pages'][:cap]),
                        'pos': jnp.asarray(runs['pos'][:cap]),
                        'mask': jnp.asarray(runs['mask'][:, :cap]),
                        'tail_pt': jnp.asarray(runs['tail_pt']),
                        'start': jnp.asarray(runs['start'])}
        db = {'positions': jnp.asarray(d['poss']),
              'page_table': st['pts']}
        if st.get('shared') is not None:
            self.stats.shared_page_reads_saved += st['saved']
            db['shared'] = st['shared']
        if fused:
            # steady-state decode feeds every row from the previous device
            # output, so (tokens, use_prev, src) are byte-stable — restage
            # only when a row resolves to host values or rows move
            fkey = (d['toks'].tobytes(), d['use_prev'].tobytes(),
                    d['src'].tobytes())
            if st.get('fkey') != fkey:
                st['fkey'] = fkey
                st['toks'] = jnp.asarray(d['toks'])
                st['use_prev'] = jnp.asarray(d['use_prev'])
                st['src'] = jnp.asarray(d['src'])
            db['tokens'] = st['toks']
            db['use_prev'] = st['use_prev']
            db['src'] = st['src']
            db['prev'] = self._prev_tokens
            if self.cfg.temperature > 0:
                db['seed'] = jnp.asarray(
                    [(self.cfg.seed * 2654435761 + next(self._seed_ctr))
                     & 0x7FFFFFFF], np.int32)
            else:
                # greedy ignores the sampling noise — stage the seed once
                if 'seed0' not in st:
                    st['seed0'] = jnp.zeros((1,), jnp.int32)
                db['seed'] = st['seed0']
        else:
            db['tokens'] = jnp.asarray(d['toks'])
        return db

    def _commit_fused(self, slots: List[DecodeSlot], toks) -> None:
        """Record a fused decode dispatch's tokens as placeholders (and,
        with an eos token, resolve them for the stop check)."""
        if hasattr(toks, 'copy_to_host_async'):
            toks.copy_to_host_async()   # overlap the eventual flush
        records: List[tuple] = []
        self._prev_tokens, self._prev_rows = toks, {}
        for i, ds in enumerate(slots):
            req = self.requests[ds.req_id]
            req.decode_steps += 1
            self._prev_rows[ds.req_id] = i
            self._append_pending(req, i, records)
        self._pending.append((toks, records))
        self._pending_rids.update(r[0] for r in records)
        if self.cfg.eos_token is not None:
            # the stop check needs token values — fetch every step (the
            # documented fused-path fallback for eos-terminated serving)
            self.flush_tokens()
            for ds in slots:
                req = self.requests[ds.req_id]
                if (req.state == ReqState.RUNNING and req.generated
                        and req.generated[-1] == self.cfg.eos_token):
                    self._finish(req)

    def _sample(self, logits):
        if self.cfg.temperature > 0:
            self._key, sub = jax.random.split(self._key)
            return sample(logits, temperature=self.cfg.temperature, key=sub)
        return sample(logits)

    def _append_pending(self, req: Request, row: int,
                        records: List[tuple]) -> None:
        """Fused-path append: the sampled value is still on device, so a
        placeholder lands in ``generated`` (patched by flush_tokens) while
        every count-based fact — fill progress, timestamps, length-based
        finish — is recorded eagerly (none of it reads the value)."""
        req.generated.append(-1)
        records.append((req.req_id, len(req.generated) - 1, row))
        if req.lease is not None:
            req.lease.note_filled(len(req.context) - 1)
        self._stamp_token(req)
        if len(req.generated) >= req.max_new_tokens:
            self._finish(req)

    def _stamp_token(self, req: Request) -> None:
        """Time and count one new token; the first closes the request's
        prefill (``prefill_s``: its first dispatch's launch → now)."""
        now = self.clock.now()
        if req.t_first_token is None:
            req.t_first_token = now
            if req.t_first_dispatch is not None:
                self.stats.prefill_s += now - req.t_first_dispatch
                self.stats.prefilled += 1
        req.t_last_token = now
        self.stats.tokens_generated += 1

    def flush_tokens(self) -> None:
        """Resolve lazily-held sampled tokens to host ints (fused path).

        The fused decode path leaves placeholders in ``Request.generated``
        and keeps values on device; anything that reads token VALUES —
        stream emission, prefill re-reads after invalidation, eos checks —
        calls this first.  No-op when nothing is pending, so callers may
        invoke it unconditionally."""
        if not self._pending:
            return
        with span(self._spans['sync']):
            for arr, records in self._pending:
                vals = np.asarray(arr)
                for rid, gi, row in records:
                    self.requests[rid].generated[gi] = int(vals[row])
        self._pending.clear()
        self._pending_rids.clear()
        self.stats.token_flushes += 1

    def _append_token(self, req: Request, tok: int) -> None:
        req.generated.append(tok)
        if req.lease is not None:
            # KV is materialized for every context token but the new one
            req.lease.note_filled(len(req.context) - 1)
        self._stamp_token(req)
        done = (len(req.generated) >= req.max_new_tokens
                or (self.cfg.eos_token is not None
                    and tok == self.cfg.eos_token))
        if done:
            self._finish(req)

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One scheduling step; returns True if any dispatch happened."""
        with span(self._spans['schedule']):
            if self._gated():
                self.stats.blocked_dispatches += 1
                return False
            batch = self.sched.schedule(self.requests, self._try_admit,
                                        self._spill)
            self.stats.steps += 1
        if batch.empty:
            return False
        if batch.prefill:
            self._dispatch_mixed(batch)
        else:
            self._dispatch_decode(batch.decode)
        return True

    def run_to_completion(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not (self.queue or self.running):
                return
            if not self.step() and self._gated():
                raise RuntimeError('offline engine gated; drive via runtime')
        raise RuntimeError('run_to_completion exceeded max_steps')

    # ------------------------------------------------------------------
    @property
    def finished(self) -> List[Request]:
        return [r for r in self.requests.values()
                if r.state == ReqState.FINISHED]

    def output_tokens(self, rid: str) -> List[int]:
        self.flush_tokens()
        return list(self.requests[rid].generated)
