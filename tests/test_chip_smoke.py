"""``chip_smoke.py``'s phases on the CPU at reduced widths.

The script's ``main()`` insists on a TPU; its phases take the node (or the
mesh and configs) from the caller, so here they run on ``reduced()``
configs — the node phase through the front-end, the Pallas-vs-oracle
logits comparison (interpreted off-TPU), and the four-chip phase on four
virtual CPU devices.
"""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, reduced
from repro.core.clock import VirtualClock
from repro.launch import compile_cache
from repro.launch.serve import build_node

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope='module')
def smoke():
    spec = importlib.util.spec_from_file_location('chip_smoke',
                                                  ROOT / 'chip_smoke.py')
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small(arch, **kw):
    return reduced(get_config(arch), page_size=4, **kw)


def test_node_phase_on_reduced_configs(smoke):
    node = build_node(_small('qwen3-0.6b'),
                      [_small('qwen3-0.6b'), _small('internlm2-1.8b')],
                      n_handles=16, pages_per_handle=8, max_seq=64,
                      prefill_chunk=16, clock=VirtualClock())
    smoke.warm_up(node, prompt_len=8, seed=0)
    stats = smoke.serve_node_phase(node, n_streams=3, prompt_len=24,
                                   max_tokens=6, n_batch=4,
                                   batch_prompt_len=20, batch_max_tokens=8,
                                   seed=0)
    assert stats['preemptions'] >= 1
    assert stats['max_preemptions_per_request'] <= 1
    assert len(stats['ttft_s']) == 3
    batch = smoke.decode_batch_from_engine(node, n_rows=3, prompt_len=30,
                                           seed=1)
    # real tables: pool pages up front, quarantine page 0 past each one,
    # and the unused rows all quarantine
    assert (batch['page_table'][:3, 0] > 0).all()
    assert (batch['page_table'][:, -1] == 0).all()
    assert (batch['page_table'][3:] == 0).all()
    assert smoke.logits_phase(node, batch) <= smoke.LOGITS_REL_BOUND
    node.drain()
    node.runtime.check_invariants()
    assert node.runtime.invalidation_routes() == []


def test_four_chip_phase_on_virtual_devices(smoke, make_virtual_mesh):
    mesh = make_virtual_mesh((4,), ('model',))
    narrow = dict(n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256)
    online = _small('qwen3-14b', **narrow)
    smoke.node_on_mesh(mesh, online, [_small('internlm2-1.8b', **narrow)], 0)
    assert smoke.mesh_vs_single(mesh, online, 0) <= smoke.LOGITS_REL_BOUND


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != 'tpu'
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ''     # no result line


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR')
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / '.jax_cache')
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update('jax_compilation_cache_dir', before)
