"""Unified model API.

``build_model(cfg)`` returns a :class:`Model` that dispatches to the family
implementation and exposes everything the launcher / dry-run / engine / tests
need: param templates (for no-allocation lowering), loss / prefill / decode
entry points, cache templates per execution shape, and ShapeDtypeStruct input
specs for every assigned (arch × shape) cell.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig, cell_supported
from repro.models import common as cm
from repro.models import dense, encdec, moe, rwkv6, zamba2

_FAMILY = {
    'dense': dense,
    'vlm': dense,
    'moe': moe,
    'ssm': rwkv6,
    'encdec': encdec,
    'hybrid': zamba2,
}

I32 = jnp.int32
BF16 = cm.DEFAULT_DTYPE


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


@dataclass
class Model:
    cfg: ModelConfig

    @property
    def mod(self):
        return _FAMILY[self.cfg.family]

    # ------------------------------------------------------------- params
    def template(self):
        return self.mod.template(self.cfg)

    def init_params(self, rng, shardings=None):
        return cm.init_from_template(self.template(), rng, shardings)

    def param_shapes(self):
        return cm.shapes_from_template(self.template())

    def param_axes(self):
        return cm.axes_from_template(self.template())

    # -------------------------------------------------------- step fns
    def loss_fn(self, params, batch, **kw):
        return self.mod.forward_train(self.cfg, params, batch, **kw)

    def prefill_fn(self, params, cache, batch):
        return self.mod.prefill(self.cfg, params, cache, batch)

    def decode_fn(self, params, cache, batch, *, long_context=False,
                  use_pallas=False):
        if self.cfg.family == 'hybrid':
            return self.mod.decode_step(self.cfg, params, cache, batch,
                                        long_context=long_context)
        if self.cfg.family in ('dense', 'vlm', 'moe'):
            # paged-KV decoder families route decode attention through the
            # Pallas paged kernel when asked (the engine's hot path)
            return self.mod.decode_step(self.cfg, params, cache, batch,
                                        use_pallas=use_pallas)
        return self.mod.decode_step(self.cfg, params, cache, batch)

    def decode_sample_fn(self, params, cache, batch, *, use_pallas=False,
                         temperature=0.0):
        """Fused decode+sampling step: (cache, (B,) int32 tokens).

        The engine's ``fused_sampling`` fast path — logits never leave the
        device (see ``models.dense.decode_step_sample``).  Dense-family
        models only; other families keep the logits-returning
        :meth:`decode_fn` + sampler composition.
        """
        assert self.cfg.family in ('dense', 'vlm'), \
            f'fused sampling not implemented for family {self.cfg.family!r}'
        return dense.decode_step_sample(self.cfg, params, cache, batch,
                                        use_pallas=use_pallas,
                                        temperature=temperature)

    # -------------------------------------------------------- caches
    def cache_template(self, shape: ShapeConfig, *, engine_pages: Optional[int] = None):
        """Cache PSpec tree for an execution shape.

        ``engine_pages`` switches to the single-device global-pool layout
        used by the serving engine (Valve's handle space).
        """
        cfg = self.cfg
        pg = cfg.page_size
        if shape is not None:
            b = shape.global_batch
            maxp = shape.seq_len // pg
            # slot 0 = quarantine; rounded up so the region dim stays
            # shardable over the 16-way model axis (padding slots unused)
            region = -(-(maxp + 1) // 16) * 16
        else:
            assert engine_pages is not None, 'need a shape or engine_pages'
            b = region = None
        fam = cfg.family

        if fam in ('dense', 'vlm', 'moe'):
            if engine_pages is not None:
                return dense.cache_template(cfg, engine_pages)
            return dense.cache_template(cfg, region, batch=b)
        if shape is None:
            raise NotImplementedError(
                f'engine pool layout only for paged-KV families, not {fam}')
        if fam == 'ssm':
            return rwkv6.cache_template(cfg, b)
        if fam == 'hybrid':
            t = {'mamba': zamba2.mamba_cache_template(cfg, b)}
            if shape.name == 'long_500k':
                t['attn'] = zamba2.attn_cache_template_dense(cfg, b, shape.seq_len)
            elif engine_pages is not None:
                t['attn'] = zamba2.attn_cache_template(cfg, engine_pages)
            else:
                t['attn'] = zamba2.attn_cache_template(cfg, region, batch=b)
            return t
        if fam == 'encdec':
            enc_len = self.enc_len(shape)
            if engine_pages is not None:
                raise NotImplementedError('engine serves decoder-only models')
            return encdec.cache_template(cfg, region, b, enc_len)
        raise ValueError(fam)

    def cache_shapes(self, shape: ShapeConfig, **kw):
        return cm.shapes_from_template(self.cache_template(shape, **kw))

    def cache_axes(self, shape: ShapeConfig, **kw):
        return cm.axes_from_template(self.cache_template(shape, **kw))

    def init_cache(self, shape: ShapeConfig, *, shardings=None, **kw):
        return cm.init_from_template(self.cache_template(shape, **kw),
                                     jax.random.PRNGKey(0), shardings)

    def serve_shardings(self, mesh, engine_pages: int):
        """(params, engine KV pool) shardings on ``mesh`` under
        SERVE_RULES, resolved against the shapes so indivisible dims
        relocate instead of failing."""
        from repro.distributed.sharding import SERVE_RULES, tree_spec_shaped
        cache = dict(engine_pages=engine_pages)
        return (tree_spec_shaped(self.param_axes(), self.param_shapes(),
                                 SERVE_RULES, mesh),
                tree_spec_shaped(self.cache_axes(None, **cache),
                                 self.cache_shapes(None, **cache),
                                 SERVE_RULES, mesh))

    def enc_len(self, shape: ShapeConfig) -> int:
        """Encoder context for enc-dec shapes (see DESIGN.md)."""
        if shape.kind == 'prefill':
            return shape.seq_len
        return min(shape.seq_len, 4096)

    # -------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the step function's ``batch``."""
        ok, why = cell_supported(self.cfg, shape)
        if not ok:
            raise ValueError(f'{self.cfg.name} × {shape.name}: {why}')
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        pg = cfg.page_size
        d = cfg.d_model

        if shape.kind == 'train':
            specs = {'tokens': _sds((b, s), I32), 'labels': _sds((b, s), I32)}
            if cfg.family == 'encdec':
                specs['frames'] = _sds((b, s, d), BF16)
            elif cfg.frontend is not None:
                specs['prefix_embeds'] = _sds((b, cfg.frontend_tokens, d), BF16)
            return specs

        if shape.kind == 'prefill':
            if cfg.family == 'encdec':
                s_dec = s // encdec.DEC_PREFIX_FRACTION
                return {
                    'frames': _sds((b, s, d), BF16),
                    'tokens': _sds((b, s_dec), I32),
                    'page_table': _sds((b, s_dec // pg), I32),
                }
            specs = {'tokens': _sds((b, s), I32),
                     'page_table': _sds((b, s // pg), I32)}
            if cfg.family == 'ssm':
                del specs['page_table']
            if cfg.frontend is not None:
                specs['prefix_embeds'] = _sds((b, cfg.frontend_tokens, d), BF16)
            return specs

        # decode: one new token with a KV cache of seq_len
        specs = {'tokens': _sds((b,), I32), 'positions': _sds((b,), I32)}
        if cfg.family == 'ssm' or shape.name == 'long_500k':
            return specs
        specs['page_table'] = _sds((b, s // pg), I32)
        return specs

    def input_axes(self, shape: ShapeConfig) -> Dict[str, tuple]:
        """Logical axes for every input (resolved via the active rule set)."""
        cfg = self.cfg
        axes = {}
        for name, spec in self.input_specs(shape).items():
            if name in ('tokens', 'labels', 'loss_mask'):
                axes[name] = ('batch', 'seq')[: len(spec.shape)] \
                    if len(spec.shape) > 1 else ('batch',)
            elif name == 'frames':
                axes[name] = ('batch', 'seq', 'embed')
            elif name == 'prefix_embeds':
                axes[name] = ('batch', None, 'embed')
            elif name == 'page_table':
                axes[name] = ('batch', None)
            elif name == 'positions':
                axes[name] = ('batch',)
            else:
                raise KeyError(name)
        return axes

    # -------------------------------------------------------- smoke inputs
    def make_inputs(self, shape_kind: str, b: int, s: int,
                    rng: Optional[np.random.Generator] = None) -> Dict[str, Any]:
        """Small *concrete* inputs for CPU smoke tests."""
        cfg = self.cfg
        rng = rng or np.random.default_rng(0)
        pg = cfg.page_size
        d = cfg.d_model
        tok = lambda shp: jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=shp), I32)

        if shape_kind == 'train':
            batch = {'tokens': tok((b, s)), 'labels': tok((b, s))}
            if cfg.family == 'encdec':
                batch['frames'] = jnp.asarray(
                    rng.normal(size=(b, s, d)) * 0.02, BF16)
            elif cfg.frontend is not None:
                p = min(cfg.frontend_tokens, s)
                batch['prefix_embeds'] = jnp.asarray(
                    rng.normal(size=(b, p, d)) * 0.02, BF16)
            return batch

        if shape_kind == 'prefill':
            maxp = s // pg
            # region-local ids; slot 0 is quarantine → pages 1..maxp
            pt = jnp.broadcast_to(jnp.arange(1, maxp + 1, dtype=I32), (b, maxp))
            if cfg.family == 'encdec':
                return {
                    'frames': jnp.asarray(rng.normal(size=(b, s, d)) * .02, BF16),
                    'tokens': tok((b, s)),
                    'page_table': pt,
                }
            batch = {'tokens': tok((b, s)), 'page_table': pt}
            if cfg.family == 'ssm':
                del batch['page_table']
            if cfg.frontend is not None:
                p = min(cfg.frontend_tokens, s)
                batch['prefix_embeds'] = jnp.asarray(
                    rng.normal(size=(b, p, d)) * .02, BF16)
            return batch

        if shape_kind == 'decode':
            maxp = s // pg
            pt = jnp.broadcast_to(jnp.arange(1, maxp + 1, dtype=I32), (b, maxp))
            return {
                'tokens': tok((b,)),
                'positions': jnp.full((b,), s - 1, I32),
                'page_table': pt,
            }
        raise ValueError(shape_kind)


@functools.lru_cache(maxsize=None)
def _build_cached(cfg: ModelConfig) -> Model:
    return Model(cfg)


def build_model(cfg: ModelConfig) -> Model:
    return _build_cached(cfg)
