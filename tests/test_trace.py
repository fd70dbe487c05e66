"""Spans and counters inside the node.

- :func:`repro.core.trace.span` is one shared null context while no
  profile is captured, and a real annotation while one is;
- the engine's time-to-first-token counters (``queue_wait_s``/``queued``,
  ``prefill_s``/``prefilled``) equal hand-computed sums on a virtual
  clock, once per request, re-admissions after invalidation included;
- a CPU profile of the front end's pump holds its turns and the engine's
  five step phases, each phase inside a turn.
"""
import asyncio
import contextlib
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.clock import VirtualClock
from repro.core.memory import MemoryPlane
from repro.core.runtime import RuntimeConfig, ValveRuntime
from repro.core.trace import span
from repro.launch.node import NodeOrchestrator
from repro.models.api import build_model
from repro.serving.engine import PHASES, Engine, EngineConfig
from repro.serving.frontend.driver import (
    PARK_SPAN, PUMP_SPAN, AsyncNodeDriver)
from repro.serving.kvpool import KVPool

ARCH = 'qwen3-0.6b'


def _engine():
    cfg = reduced(get_config(ARCH), page_size=4)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    pool = KVPool(12, 4, page_size=4, reserved_handles=1)
    clock = VirtualClock()
    eng = Engine(model, params, pool,
                 EngineConfig(max_batch=4, max_seq=64, prefill_chunk=8),
                 clock=clock)
    return eng, pool, clock


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 500, n).tolist()


# -- the helper --------------------------------------------------------------
def test_span_is_one_shared_null_context_while_not_profiling():
    a, b = span('engine.step.stage:x'), span('driver.pump', rows=3)
    assert a is b
    assert isinstance(a, contextlib.nullcontext)
    with a:
        with b:             # reentrant: nested spans share the one object
            pass


def test_span_is_an_annotation_while_profiling(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        s = span('engine.step.launch:x', rows=2)
        assert isinstance(s, jax.profiler.TraceAnnotation)
        with s:
            pass
    assert isinstance(span('engine.step.launch:x'), contextlib.nullcontext)


# -- the engine's names ------------------------------------------------------
def test_engine_outside_a_node_is_named_by_class_and_model():
    eng, _, _ = _engine()
    name = f'offline:{eng.mcfg.name}'
    assert eng.name == name
    assert eng._spans == {p: f'engine.step.{p}:{name}' for p in PHASES}


@pytest.mark.parametrize('given', [None, 'online:chat'])
def test_node_gives_the_engine_its_key(given):
    pool = KVPool(6, 4, page_size=4, reserved_handles=1)
    node = NodeOrchestrator(ValveRuntime(pool, RuntimeConfig(),
                                         clock=VirtualClock()))
    eng = node.add_engine(reduced(get_config(ARCH), page_size=4),
                          EngineConfig(max_batch=2, max_seq=32,
                                       prefill_chunk=8, klass='online'),
                          name=given)
    (key,) = node.names
    assert eng.name == key == (given or f'online:{eng.mcfg.name}#0')
    assert eng._spans['launch'] == f'engine.step.launch:{key}'


# -- the counters ------------------------------------------------------------
def test_queue_wait_and_prefill_counters_by_hand():
    """A (20 tokens) is submitted at 0, B (12) at 1.0; the first dispatch
    launches at 2.5 with a chunk of each.  B's last chunk runs at 2.75, A's
    at 3.25: queue wait 2.5 + 1.5, prefill 0.25 + 0.75."""
    eng, _, clock = _engine()
    a = eng.submit(_prompt(20, 1), max_new_tokens=3)
    clock.advance(1.0)
    b = eng.submit(_prompt(12, 2), max_new_tokens=3)
    clock.advance_to(2.5)
    assert eng.step()
    st = eng.stats
    assert (st.queue_wait_s, st.queued) == (pytest.approx(4.0), 2)
    assert (st.prefill_s, st.prefilled) == (0.0, 0)
    assert eng.requests[a].t_first_dispatch == eng.requests[b].t_first_dispatch
    clock.advance_to(2.75)
    assert eng.step()
    assert (st.prefill_s, st.prefilled) == (pytest.approx(0.25), 1)
    clock.advance_to(3.25)
    assert eng.step()
    assert (st.prefill_s, st.prefilled) == (pytest.approx(1.0), 2)
    eng.run_to_completion()
    assert (st.queue_wait_s, st.queued) == (pytest.approx(4.0), 2)
    assert (st.prefill_s, st.prefilled) == (pytest.approx(1.0), 2)
    assert eng.requests[b].ttft == pytest.approx(1.75)


@pytest.mark.parametrize('after_tokens', [0, 2])
def test_readmission_after_invalidation_is_not_counted_twice(after_tokens):
    """Invalidated mid-prefill (0 tokens) or while decoding (2 tokens): the
    re-admitted request's dispatches add no queue wait, and its prefill is
    counted once, from its first dispatch to its first token."""
    eng, pool, clock = _engine()
    clock.advance_to(1.0)
    rid = eng.submit(_prompt(20, 3), max_new_tokens=6)
    clock.advance_to(2.0)
    assert eng.step()                   # launch at 2.0: chunk 0..8
    for _ in range(10):
        if len(eng.requests[rid].generated) >= after_tokens:
            break
        clock.advance(0.5)
        eng.step()
    inv = MemoryPlane.of(pool).reclaim_handles(pool.handles_of_request(rid))
    assert rid in inv
    eng.on_pages_invalidated(inv)
    assert eng.requests[rid].recomputes == 1
    t_inv = clock.now()
    clock.advance(0.5)
    eng.run_to_completion()
    st, req = eng.stats, eng.requests[rid]
    assert (st.queue_wait_s, st.queued) == (pytest.approx(1.0), 1)
    assert req.t_first_dispatch == 2.0
    assert st.prefilled == 1
    assert st.prefill_s == pytest.approx(req.t_first_token - 2.0)
    if after_tokens:                    # its first token came before
        assert req.t_first_token <= t_inv
    else:                               # the recompute made it
        assert req.t_first_token == pytest.approx(t_inv + 0.5)


# -- the spans in a profile --------------------------------------------------
ONLINE = 'online:chat'


def _node():
    pool = KVPool(8, 4, page_size=4, reserved_handles=1)
    rt = ValveRuntime(pool, RuntimeConfig(n_devices=1, t_cool_init=0.002),
                      clock=VirtualClock())
    node = NodeOrchestrator(rt, idle_advance=1e-3)
    for klass, name in (('online', ONLINE), ('offline', 'offline0:batch')):
        node.add_engine(reduced(get_config(ARCH), page_size=4),
                        EngineConfig(max_batch=4, max_seq=48,
                                     prefill_chunk=8, klass=klass),
                        seed=0, name=name)
    return node


def _host_spans(log_dir):
    """(name, start, end, metadata) of the driver's and the engines'
    spans."""
    (path,) = glob.glob(f'{log_dir}/**/*.xplane.pb', recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                        for ev in line.events
                        if ev.name.startswith(('driver.', 'engine.step'))]
    return sorted(out, key=lambda h: h[1])


async def _serve(node, mode):
    if mode == 'drain':
        driver = AsyncNodeDriver(node)
        stream = driver.submit_stream(_prompt(11, 4), max_new_tokens=4)
        await driver.drain()
        return stream.req_id, await stream.collect()
    async with AsyncNodeDriver(node) as driver:
        stream = driver.submit_stream(_prompt(11, 4), max_new_tokens=4)
        tokens = await stream.collect()
        while node.has_work():
            await asyncio.sleep(0)
        await asyncio.sleep(0)          # the idle pump parks
        assert driver.stats.idle_parks
        return stream.req_id, tokens


@pytest.mark.parametrize('mode', ['drain', 'pump'])
def test_profile_holds_pump_turns_and_step_phases(tmp_path, mode):
    node = _node()
    node.offline[0].submit(_prompt(9, 5), max_new_tokens=3)
    with jax.profiler.trace(str(tmp_path)):
        rid, tokens = asyncio.run(asyncio.wait_for(_serve(node, mode), 120))
    assert len(tokens) == 4
    spans = _host_spans(tmp_path)
    turns = [(s, e) for n, s, e, _ in spans if n == PUMP_SPAN]
    assert turns
    phases = [(n, s, e) for n, s, e, _ in spans
              if n.startswith('engine.step.')]
    names = {n for n, _, _ in phases}
    assert {f'engine.step.{p}:{ONLINE}' for p in PHASES} <= names
    for n, s, e in phases:
        assert any(a <= s and e <= b for a, b in turns), n
    # the launch names its rows, and on a mixed dispatch its prefill rows'
    # requests: the online request's first dispatch carries it
    launches = [m for n, _, _, m in spans
                if n == f'engine.step.launch:{ONLINE}']
    assert all(m['rows'] >= 1 for m in launches)
    assert any(m.get('prefill') == rid for m in launches)
    parks = [s for n, s, _, _ in spans if n == PARK_SPAN]
    if mode == 'pump':
        assert parks
    assert not any(a < p < b for p in parks for a, b in turns)
