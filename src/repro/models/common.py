"""Shared model building blocks.

Params are plain nested dicts of arrays.  Each family builds a *template* —
the same nested structure with :class:`PSpec` leaves carrying shape, logical
sharding axes, and init law — from which we derive:

- real params (``init_from_template``) for smoke tests / small runs,
- ``jax.ShapeDtypeStruct`` stand-ins (``shapes_from_template``) so the dry-run
  lowers full-size models without allocating a byte,
- logical-axes trees (``axes_from_template``) → PartitionSpecs for pjit.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain

DEFAULT_DTYPE = jnp.bfloat16


@dataclass(frozen=True)
class PSpec:
    """Param template leaf: shape + logical axes + init law."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = 'normal'       # 'normal' | 'zeros' | 'ones'
    scale: Optional[float] = None  # None → 1/sqrt(fan_in) for 'normal'
    dtype: Any = DEFAULT_DTYPE

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_pspec(x):
    return isinstance(x, PSpec)


def _init_leaf(leaf: PSpec, key: jax.Array):
    if leaf.init == 'zeros':
        return jnp.zeros(leaf.shape, leaf.dtype)
    if leaf.init == 'ones':
        return jnp.ones(leaf.shape, leaf.dtype)
    fan_in = leaf.shape[-2] if len(leaf.shape) >= 2 else leaf.shape[-1]
    scale = leaf.scale if leaf.scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, leaf.shape, jnp.float32) * scale
            ).astype(leaf.dtype)


def init_from_template(tmpl, rng: jax.Array, shardings=None):
    """Materialize a template.  With ``shardings`` (a matching tree of
    ``jax.sharding.Sharding``) each leaf is generated by its own jitted
    program straight into its sharding, so a model too large for one
    device never exists whole on any device."""
    leaves, treedef = jax.tree.flatten(tmpl, is_leaf=_is_pspec)
    keys = jax.random.split(rng, len(leaves))
    if shardings is None:
        out = [_init_leaf(leaf, key) for leaf, key in zip(leaves, keys)]
    else:
        out = [jax.jit(functools.partial(_init_leaf, leaf),
                       out_shardings=sh)(key)
               for leaf, key, sh in zip(leaves, keys,
                                        treedef.flatten_up_to(shardings))]
    return jax.tree.unflatten(treedef, out)


def shapes_from_template(tmpl):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tmpl, is_leaf=_is_pspec)


def axes_from_template(tmpl):
    return jax.tree.map(lambda l: l.axes, tmpl, is_leaf=_is_pspec)


def param_bytes(tmpl) -> int:
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tmpl, is_leaf=_is_pspec))


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotate-half RoPE.  x: (..., S, H, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # positions: (B, S) → angles (B, S, 1, half)
    angles = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    xr1 = x1.astype(jnp.float32) * cos - x2.astype(jnp.float32) * sin
    xr2 = x2.astype(jnp.float32) * cos + x1.astype(jnp.float32) * sin
    return jnp.concatenate([xr1, xr2], axis=-1).astype(x.dtype)


def swiglu(x, wg, wu, wd, bg=None, bu=None, bd=None):
    g = x @ wg
    u = x @ wu
    if bg is not None:
        g = g + bg
        u = u + bu
    axes = ('batch', 'seq', 'ffn') if g.ndim == 3 else ('batch', 'ffn')
    g = constrain(g, axes)
    u = constrain(u, axes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = h @ wd
    if bd is not None:
        out = out + bd
    return out


def repeat_kv(kv, groups: int):
    """(..., S, Hkv, Dh) → (..., S, Hkv*groups, Dh)."""
    if groups == 1:
        return kv
    return jnp.repeat(kv, groups, axis=-2)


def attention(q, k, v, *, q_positions, kv_positions, kv_valid=None,
              causal: bool = True, scale: Optional[float] = None):
    """Reference GQA attention (jnp oracle path).

    q: (B, Sq, Hq, Dh); k/v: (B, Skv, Hkv, Dh).  f32 softmax.
    """
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    groups = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    qg = q.reshape(b, sq, hkv, groups, dh)
    # f32 ACCUMULATION without upcasting operands: upcasting k/v first
    # materializes f32 copies of the (gathered) KV — 2× the HBM traffic
    # and temp footprint on every attention (§Perf H-mem3)
    scores = jnp.einsum('bqhgd,bkhd->bhgqk', qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((b, 1, 1, sq, k.shape[1]), bool)
    if causal:
        mask &= (q_positions[:, None, None, :, None]
                 >= kv_positions[:, None, None, None, :])
    if kv_valid is not None:
        mask &= kv_valid[:, None, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum('bhgqk,bkhd->bqhgd', probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def chunked_attention(q, k, v, *, q_positions, kv_positions, kv_valid=None,
                      causal: bool = True, scale: Optional[float] = None,
                      q_chunk: int = 512, remat_chunks: bool = True):
    """Blockwise attention: scan over Q chunks so scores never materialize at
    (Sq × Skv).  Same math as :func:`attention` (oracle-equivalent).

    ``remat_chunks`` checkpoints each chunk body: without it the backward
    pass keeps EVERY chunk's f32 scores/probs live simultaneously
    (≈ n_chunks × B·H·q_chunk·Skv f32 — the dominant HBM temp the dry-run
    found on big train cells); with it the live set is one chunk,
    recomputed during backprop (§Perf H-mem2).
    """
    b, sq, hq, dh = q.shape
    if sq <= q_chunk:
        return attention(q, k, v, q_positions=q_positions,
                         kv_positions=kv_positions, kv_valid=kv_valid,
                         causal=causal, scale=scale)
    n = sq // q_chunk
    assert sq % q_chunk == 0, (sq, q_chunk)
    qs = q.reshape(b, n, q_chunk, hq, dh).transpose(1, 0, 2, 3, 4)
    qp = q_positions.reshape(b, n, q_chunk).transpose(1, 0, 2)

    def body(_, xs):
        qc, qpc = xs
        out = attention(qc, k, v, q_positions=qpc, kv_positions=kv_positions,
                        kv_valid=kv_valid, causal=causal, scale=scale)
        return None, out

    if remat_chunks:
        body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (qs, qp))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dh)


def chunked_ce_loss(h, norm_w, unembed, labels, *, mask=None, eps: float = 1e-5,
                    seq_chunk: int = 512, logit_axes=('batch', 'seq', 'vocab')):
    """Final-norm → unembed → cross-entropy, scanned over sequence chunks so
    the (B, S, V) logits tensor never materializes.

    Returns (sum_nll, sum_count) so callers can combine across microbatches.
    """
    b, s, d = h.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    n = max(s // seq_chunk, 1)
    seq_chunk = s // n
    assert s % n == 0
    hs = h.reshape(b, n, seq_chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, seq_chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, n, seq_chunk).transpose(1, 0, 2)

    def body(carry, xs):
        nll_sum, cnt = carry
        hc, lc, mc = xs
        hc = rms_norm(hc, norm_w, eps)
        logits = hc @ unembed
        logits = constrain(logits, logit_axes)
        logits = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        mcf = mc.astype(jnp.float32)
        nll_sum = nll_sum + jnp.sum((lse - gold) * mcf)
        cnt = cnt + jnp.sum(mcf)
        return (nll_sum, cnt), None

    # checkpoint: otherwise every chunk's (B, chunk, V) f32 logits stay
    # live for the backward pass simultaneously (§Perf H-mem2)
    body = jax.checkpoint(body)
    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls, ms))
    return nll, cnt


def softmax_cross_entropy(logits, labels, mask=None):
    """logits (B, S, V) [any dtype], labels (B, S) int32 → mean NLL (f32)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Paged KV-cache primitives (the substrate Valve's reclamation operates on).
# Pool layout: (P, page, Hkv, Dh); page 0 is the QUARANTINE page.  Page tables
# hold *physical* page ids; remapping a victim handle = rewriting its entries
# to 0, which is always mapped, so no access can ever fault (paper §5).
# ---------------------------------------------------------------------------

QUARANTINE_PAGE = 0


def paged_gather(pool, page_table):
    """pool (P, pg, Hkv, Dh), page_table (B, maxp) → (B, maxp*pg, Hkv, Dh)."""
    b, maxp = page_table.shape
    pg = pool.shape[1]
    gathered = pool[page_table]              # (B, maxp, pg, Hkv, Dh)
    return gathered.reshape(b, maxp * pg, *pool.shape[2:])


def paged_write_prefill(pool, page_table, kv):
    """Write a full prefill's K or V into the pool.

    kv: (B, S, Hkv, Dh) with S % page == 0; page_table (B, S//page) physical ids.
    """
    b, s, hkv, dh = kv.shape
    pg = pool.shape[1]
    chunks = kv.reshape(b * (s // pg), pg, hkv, dh)
    idx = page_table[:, : s // pg].reshape(-1)
    return pool.at[idx].set(chunks, mode='drop')


def paged_write_token(pool, page_ids, offsets, kv):
    """Write one new token per request.  kv: (B, Hkv, Dh)."""
    return pool.at[page_ids, offsets].set(kv, mode='drop')


def region_gather(pool, page_table):
    """Region-paged gather (SPMD-clean: batch-aligned take_along_axis).

    pool (B, R, pg, Hkv, Dh), page_table (B, maxp) with region-local ids
    → (B, maxp*pg, Hkv, Dh)."""
    b, maxp = page_table.shape
    idx = page_table[:, :, None, None, None]
    gathered = jnp.take_along_axis(pool, idx, axis=1)   # (B, maxp, pg, H, D)
    return gathered.reshape(b, maxp * pool.shape[2], *pool.shape[3:])


def kv_gather(pool, page_table):
    """Dispatch on layout: 4-D = global pool, 5-D = per-request regions."""
    return (paged_gather if pool.ndim == 4 else region_gather)(pool, page_table)


def kv_write_prefill(pool, page_table, kv):
    """Layout-dispatching prefill write.  kv: (B, S, Hkv, Dh)."""
    if pool.ndim == 4:
        return paged_write_prefill(pool, page_table, kv)
    b, s, hkv, dh = kv.shape
    pg = pool.shape[2]
    np_ = s // pg
    chunks = kv.reshape(b, np_, pg, hkv, dh)
    bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
    return pool.at[bidx, page_table[:, :np_]].set(chunks, mode='drop')


def kv_write_token(pool, page_ids, offsets, kv):
    """Layout-dispatching single-token write.  kv: (B, Hkv, Dh)."""
    if pool.ndim == 4:
        return paged_write_token(pool, page_ids, offsets, kv)
    bidx = jnp.arange(pool.shape[0], dtype=jnp.int32)
    return pool.at[bidx, page_ids, offsets].set(kv, mode='drop')


def kv_write_tokens(pool, page_ids, offsets, kv):
    """Token-granular chunk write (no page-alignment requirement).

    page_ids/offsets: (B, C) per-token physical page + in-page offset;
    kv: (B, C, Hkv, Dh).  Padding tokens should point at the quarantine page
    (id 0) — overwriting quarantine is harmless by design.
    """
    if pool.ndim == 4:
        b, c = page_ids.shape
        return pool.at[page_ids.reshape(-1), offsets.reshape(-1)].set(
            kv.reshape(b * c, *kv.shape[2:]), mode='drop')
    bidx = jnp.arange(pool.shape[0], dtype=jnp.int32)[:, None]
    return pool.at[bidx, page_ids, offsets].set(kv, mode='drop')


def paged_attention_ref(q, pool_k, pool_v, page_table, lengths, *,
                        scale: Optional[float] = None):
    """Decode attention through the page table (pure-jnp oracle).

    q: (B, Hq, Dh) — one new token per request at position ``lengths``.
    Pool layout may be global (P, pg, H, D) or region (B, R, pg, H, D).
    """
    b, hq, dh = q.shape
    pg = pool_k.shape[-3]
    maxp = page_table.shape[1]
    k = kv_gather(pool_k, page_table)   # (B, S_max, Hkv, Dh)
    v = kv_gather(pool_v, page_table)
    kv_pos = jnp.broadcast_to(jnp.arange(maxp * pg, dtype=jnp.int32), (b, maxp * pg))
    valid = kv_pos < lengths[:, None]
    out = attention(q[:, None], k, v,
                    q_positions=lengths[:, None].astype(jnp.int32),
                    kv_positions=kv_pos, kv_valid=valid,
                    causal=False, scale=scale)
    return out[:, 0]
