"""The main-path kernels and one whole decode step compile for TPU v5e.

Nothing here runs: each program is lowered from shapes placed on a v5e
chip that is described, not attached, and compiled by the TPU compiler
installed with jax — which refuses what the Pallas interpreter accepts
(block shapes off the (8, 128) tiling, VMEM overuse, unpartitionable
kernels).  Shapes are the published widths the node serves: page size 16,
8 decode rows, a 512-token budget, a 513-page pool.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import common as kc
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import (
    paged_attention, paged_attention_prefix_shared)
from repro.kernels.sampling.ops import fused_unembed_sample
from repro.models.api import build_model

ARCHS = ('qwen3-0.6b', 'internlm2-1.8b')
BATCH, MAX_SEQ, POOL_PAGES = 8, 512, 513


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_count(compiled) -> int:
    return compiled.as_text().count('tpu_custom_call')


@pytest.mark.parametrize('arch', ARCHS)
def test_paged_decode_kernel_compiles(one_chip, arch):
    cfg = get_config(arch)
    pg = cfg.page_size
    pool = _sds(one_chip, (POOL_PAGES, pg, cfg.n_kv_heads, cfg.hd),
                jnp.bfloat16)
    compiled = _compile(
        lambda *a: paged_attention(*a, interpret=False),
        _sds(one_chip, (BATCH, cfg.n_heads, cfg.hd), jnp.bfloat16),
        pool, pool,
        _sds(one_chip, (BATCH, MAX_SEQ // pg), jnp.int32),
        _sds(one_chip, (BATCH,), jnp.int32))
    assert _kernel_count(compiled) >= 1


@pytest.mark.parametrize('arch', ARCHS)
def test_prefix_shared_kernel_compiles(one_chip, arch):
    cfg = get_config(arch)
    pg, slots = cfg.page_size, 8
    pool = _sds(one_chip, (POOL_PAGES, pg, cfg.n_kv_heads, cfg.hd),
                jnp.bfloat16)
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    compiled = _compile(
        lambda *a: paged_attention_prefix_shared(*a, backend='pallas',
                                                 interpret=False),
        _sds(one_chip, (BATCH, cfg.n_heads, cfg.hd), jnp.bfloat16),
        pool, pool, i32(slots), i32(slots),
        _sds(one_chip, (BATCH, slots), jnp.float32),
        i32(BATCH, MAX_SEQ // pg), i32(BATCH), i32(BATCH))
    assert _kernel_count(compiled) >= 2     # shared-run pass + tail walk


@pytest.mark.parametrize('arch', ARCHS)
def test_fused_unembed_sample_compiles(one_chip, arch):
    cfg = get_config(arch)
    compiled = _compile(
        lambda h, w: fused_unembed_sample(h, w, 0, backend='pallas',
                                          interpret=False),
        _sds(one_chip, (BATCH, cfg.d_model), jnp.bfloat16),
        _sds(one_chip, (cfg.d_model, cfg.vocab_size), jnp.bfloat16))
    assert _kernel_count(compiled) >= 1


def test_flash_attention_compiles(one_chip):
    q = _sds(one_chip, (2, 256, 16, 128), jnp.bfloat16)
    kv = _sds(one_chip, (2, 256, 8, 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False), q, kv, kv)
    assert _kernel_count(compiled) >= 1


def test_engine_decode_step_compiles_with_pallas(one_chip, monkeypatch):
    """The Engine's decode program (``model.decode_fn(use_pallas=True)``,
    KV pool donated) at qwen3-0.6b width.  The kernel's interpret switch
    resolves from the process's backend — the CPU here — so the test
    steers it to the compiled kernel the chip would take."""
    monkeypatch.setattr(kc, 'resolve_interpret',
                        lambda interpret: bool(interpret))
    model = build_model(get_config('qwen3-0.6b'))
    place = lambda tree: jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    pg = model.cfg.page_size
    batch = {'tokens': _sds(one_chip, (BATCH,), jnp.int32),
             'positions': _sds(one_chip, (BATCH,), jnp.int32),
             'page_table': _sds(one_chip, (BATCH, MAX_SEQ // pg), jnp.int32)}
    compiled = jax.jit(
        lambda p, c, b: model.decode_fn(p, c, b, use_pallas=True),
        donate_argnums=(1,)).lower(
            place(model.param_shapes()),
            place(model.cache_shapes(None, engine_pages=POOL_PAGES)),
            batch).compile()
    assert _kernel_count(compiled) >= 1
