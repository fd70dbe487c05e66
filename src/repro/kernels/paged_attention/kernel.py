"""Paged decode-attention Pallas TPU kernel.

One new token per request attends over its KV cache *through the page
table* — the indirection Valve's quarantine remap rewrites.  The page table
and per-request lengths ride in scalar-prefetch SMEM
(PrefetchScalarGridSpec), and the K/V BlockSpec index maps dereference
``page_table[b, ip]`` to pick the physical page, so the gather never
materializes in HBM: pages stream HBM→VMEM one whole (page_size × Hkv × Dh)
page at a time while the online-softmax state sits in VMEM scratch.

Grid ``(B, n_pages)``; pages is innermost/sequential.  Each step fetches
one physical page for *every* kv head — the block's last two dims are the
pool's full (Hkv, Dh), which is what the TPU lowering's (8, 128) tiling
rule admits without relaying out the pool — and loops over the kv heads
inside the kernel with per-head (G,) / (G, Dh) softmax state.  Tokens past
a request's length are masked in-kernel; a quarantined page (id 0) streams
garbage that is either masked (healthy request) or discarded by Valve's
invalidation-recompute contract — never a fault, by construction.

GQA: q for one (b, kv-head) is the (group, Dh) block of query heads.
Shared machinery (online softmax, length masking) comes from
:mod:`repro.kernels.common`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as kc


def _walk_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *, page_index,
               kv_len, page_size: int, scale: float) -> None:
    """Fold one fetched page into every kv head's running softmax state.
    q_ref: (1, Hkv, G, D); k/v_ref: (1, pg, Hkv, D); state: (Hkv, G[, D])."""
    for h in range(q_ref.shape[1]):
        q = q_ref[0, h].astype(jnp.float32)               # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)         # (pg, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)         # (pg, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = kc.block_positions(page_index, page_size, s.shape, 1)
        s = kc.mask_block_scores(s, k_pos=pos, kv_len=kv_len)
        m_ref[h], l_ref[h], acc_ref[h] = kc.online_softmax_update(
            s, v, m_ref[h], l_ref[h], acc_ref[h])


def _flush_heads(o_ref, l_ref, acc_ref) -> None:
    for h in range(o_ref.shape[1]):
        o_ref[0, h] = kc.online_softmax_finalize(
            acc_ref[h], l_ref[h]).astype(o_ref.dtype)


def _state_scratch(hkv: int, rows: int, d: int):
    return [pltpu.VMEM((hkv, rows), jnp.float32),
            pltpu.VMEM((hkv, rows), jnp.float32),
            pltpu.VMEM((hkv, rows, d), jnp.float32)]


def _paged_kernel(page_table_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, scale: float):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        kc.online_softmax_init(m_ref, l_ref, acc_ref)

    _walk_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, page_index=ip,
               kv_len=lengths_ref[b], page_size=page_size, scale=scale)

    @pl.when(ip == pl.num_programs(1) - 1)
    def _flush():
        _flush_heads(o_ref, l_ref, acc_ref)


def _page_spec(pg: int, hkv: int, d: int, page_of):
    """K/V block: one whole physical page (all kv heads) chosen by
    ``page_of(grid indices, *scalar refs)`` — the page-table dereference."""
    return pl.BlockSpec((1, pg, hkv, d),
                        lambda *a: (page_of(*a), 0, 0, 0))


def _table_page(ib, ip, page_table, *_):
    """Physical page of request ``ib``'s ``ip``-th table entry."""
    return page_table[ib, ip]


def _row_spec(*shape):
    """Request ``ib``'s block (grid axis 0) of a (B, *shape) array."""
    return pl.BlockSpec((1,) + shape,
                        lambda ib, *_: (ib,) + (0,) * len(shape))


def _whole_spec(shape):
    """The whole array as one block, at every grid step."""
    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))


def paged_attention_bhgd(q, pool_k, pool_v, page_table, lengths, *,
                         scale: Optional[float] = None,
                         interpret: Optional[bool] = None):
    """q: (B, Hkv, G, D); pools: (P, pg, Hkv, D) — global paged layout;
    page_table: (B, maxp) physical ids (0 = quarantine); lengths: (B,)."""
    b, hkv, g, d = q.shape
    pg = pool_k.shape[1]
    maxp = page_table.shape[1]
    scale = d ** -0.5 if scale is None else scale
    interpret = kc.resolve_interpret(interpret)

    page = _page_spec(pg, hkv, d, _table_page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxp),
        in_specs=[_row_spec(hkv, g, d), page, page],
        out_specs=_row_spec(hkv, g, d),
        scratch_shapes=_state_scratch(hkv, g, d),
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page_size=pg, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
    )(page_table, lengths, q, pool_k, pool_v)


# ---------------------------------------------------------------------------
# Prefix-shared-aware variant: two online-softmax phases merged through the
# associativity of the running (m, l, acc) state.  Phase 1 streams each
# DEDUPED shared physical page once and scores it against the whole batch's
# queries (per-row participation mask); phase 2 is the stock per-request
# page walk over the tails, seeded from phase 1's partial state instead of
# the (−inf, 0, 0) init.  Inputs come from
# :func:`repro.kernels.paged_attention.prefix.build_shared_runs`.
# ---------------------------------------------------------------------------

def _shared_run_kernel(shared_pages_ref, share_pos_ref, q_ref, k_ref, v_ref,
                       mask_ref, m_out_ref, l_out_ref, acc_out_ref,
                       m_ref, l_ref, acc_ref, *, page_size: int,
                       scale: float):
    """q_ref: (Hkv, B·G, D) — the whole batch's queries per kv head;
    mask_ref: (1, B·G, 1) this slot's participation per query row."""
    js = pl.program_id(0)

    @pl.when(js == 0)
    def _init():
        kc.online_softmax_init(m_ref, l_ref, acc_ref)

    # participation mask: rows not sharing this slot (and quarantine
    # padding slots) score NEG_INF.  A row masked at every slot so far
    # carries garbage mass at m = NEG_INF; the first finite score — here
    # or in the tail phase — rescales it away (alpha = exp(-inf) = 0), so
    # no explicit reset is needed.  Shared pages are fully filled by the
    # publication contract, so no kv_len mask applies in this phase.
    ok = mask_ref[0] > 0                                 # (B·G, 1)
    for h in range(q_ref.shape[0]):
        q = q_ref[h].astype(jnp.float32)                 # (B·G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)        # (pg, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok, s, kc.NEG_INF)
        m_ref[h], l_ref[h], acc_ref[h] = kc.online_softmax_update(
            s, v, m_ref[h], l_ref[h], acc_ref[h])

    @pl.when(js == pl.num_programs(0) - 1)
    def _flush():
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]
        acc_out_ref[...] = acc_ref[...]


def _tail_kernel(tail_pt_ref, start_ref, lengths_ref, q_ref, k_ref, v_ref,
                 m0_ref, l0_ref, acc0_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 page_size: int, scale: float):
    b = pl.program_id(0)
    ip = pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        # resume the online softmax from the shared-run partial state
        m_ref[...] = m0_ref[0]
        l_ref[...] = l0_ref[0]
        acc_ref[...] = acc0_ref[0]

    # tail pages sit AFTER the row's shared run: shift by start_pages
    _walk_page(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
               page_index=start_ref[b] + ip, kv_len=lengths_ref[b],
               page_size=page_size, scale=scale)

    @pl.when(ip == pl.num_programs(1) - 1)
    def _flush():
        _flush_heads(o_ref, l_ref, acc_ref)


def paged_attention_prefix_shared_bhgd(q, pool_k, pool_v, shared_pages,
                                       share_pos, share_mask, tail_pt,
                                       start_pages, lengths, *,
                                       scale: Optional[float] = None,
                                       interpret: Optional[bool] = None):
    """q: (B, Hkv, G, D); pools: (P, pg, Hkv, D); shared_pages/share_pos:
    (S,); share_mask: (B, S) f32; tail_pt: (B, maxp); start_pages,
    lengths: (B,).  See ``prefix.build_shared_runs`` for the structure."""
    b, hkv, g, d = q.shape
    pg = pool_k.shape[1]
    n_slots = shared_pages.shape[0]
    maxp = tail_pt.shape[1]
    rows = b * g
    scale = d ** -0.5 if scale is None else scale
    interpret = kc.resolve_interpret(interpret)

    # phase 1: grid (S,) — each shared physical page streams HBM→VMEM
    # exactly once for the WHOLE batch and every kv head.  The batch's
    # queries are regrouped per kv head, and the participation mask per
    # query row, so every block's last two dims are the full array dims
    # (tiny XLA relayouts of q and the mask, never of the pool).
    q_rows = q.transpose(1, 0, 2, 3).reshape(hkv, rows, d)
    row_mask = jnp.repeat(share_mask.T, g, axis=1)[:, :, None]  # (S, B·G, 1)
    state = [jax.ShapeDtypeStruct((hkv, rows), jnp.float32),
             jax.ShapeDtypeStruct((hkv, rows), jnp.float32),
             jax.ShapeDtypeStruct((hkv, rows, d), jnp.float32)]
    shared_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots,),
        in_specs=[
            _whole_spec((hkv, rows, d)),
            _page_spec(pg, hkv, d, lambda js, sp, spos: sp[js]),
            _page_spec(pg, hkv, d, lambda js, sp, spos: sp[js]),
            pl.BlockSpec((1, rows, 1), lambda js, sp, spos: (js, 0, 0)),
        ],
        out_specs=[_whole_spec(s.shape) for s in state],
        scratch_shapes=_state_scratch(hkv, rows, d),
    )
    m0, l0, acc0 = pl.pallas_call(
        functools.partial(_shared_run_kernel, page_size=pg, scale=scale),
        grid_spec=shared_spec,
        out_shape=state,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
    )(shared_pages, share_pos, q_rows, pool_k, pool_v, row_mask)
    # back to per-request blocks for the tail walk
    m0 = m0.reshape(hkv, b, g).transpose(1, 0, 2)
    l0 = l0.reshape(hkv, b, g).transpose(1, 0, 2)
    acc0 = acc0.reshape(hkv, b, g, d).transpose(1, 0, 2, 3)

    # phase 2: the stock per-request page walk over the tails, resuming
    # from phase 1's partial (m, l, acc)
    page = _page_spec(pg, hkv, d, _table_page)
    tail_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, maxp),
        in_specs=[_row_spec(hkv, g, d), page, page, _row_spec(hkv, g),
                  _row_spec(hkv, g), _row_spec(hkv, g, d)],
        out_specs=_row_spec(hkv, g, d),
        scratch_shapes=_state_scratch(hkv, g, d),
    )
    return pl.pallas_call(
        functools.partial(_tail_kernel, page_size=pg, scale=scale),
        grid_spec=tail_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
    )(tail_pt, start_pages, lengths, q, pool_k, pool_v, m0, l0, acc0)
