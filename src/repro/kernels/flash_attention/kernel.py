"""Flash-attention Pallas TPU kernel (prefill/train hot spot).

Grid ``(B·Hq, n_q_blocks, n_kv_blocks)`` — the kv axis is innermost and
sequential ('arbitrary'); online-softmax running state (m, l, acc) lives in
VMEM scratch and is carried across kv steps, so scores never materialize in
HBM (the dominant traffic term the dry-run finds on the XLA oracle path).

GQA is handled in the K/V BlockSpec index maps (``h // group``) — no KV
head replication is materialized.  Causal blocks above the diagonal are
masked in-kernel; with a Mosaic grid the skipped blocks cost ~nothing on the
MXU because every lane is masked (a fully-skipped variant would use
``pl.when`` on the block index).

Block sizes default to (128, 128): q/k/v tiles of 128×Dh bf16 keep the
working set ≤ ~200 KB in VMEM at Dh=128 and align to the 128-lane MXU.
Shared machinery (online softmax, masking, padding) comes from
:mod:`repro.kernels.common` — this file contains only the flash-specific
grid/BlockSpec layout.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as kc


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, scale: float, block_q: int, block_k: int,
                  kv_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        kc.online_softmax_init(m_ref, l_ref, acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # (Bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (Bk, D)
    v = v_ref[0].astype(jnp.float32)                  # (Bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = kc.block_positions(iq, block_q, s.shape, 0)
    k_pos = kc.block_positions(ik, block_k, s.shape, 1)
    s = kc.mask_block_scores(s, q_pos=q_pos, k_pos=k_pos, causal=causal,
                             kv_len=kv_len)

    m_ref[...], l_ref[...], acc_ref[...] = kc.online_softmax_update(
        s, v, m_ref[...], l_ref[...], acc_ref[...])

    @pl.when(ik == nk - 1)
    def _flush():
        o_ref[0] = kc.online_softmax_finalize(
            acc_ref[...], l_ref[...]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: Optional[bool] = None):
    """q: (BHq, Sq, D); k/v: (BHkv, Skv, D); BHq = BHkv · group.

    Heads are flattened batch-major (b·H + h) so the kv index map recovers
    (b, h // group) arithmetically.
    """
    bhq, sq, d = q.shape
    bhkv, skv, _ = k.shape
    assert bhq % bhkv == 0, (bhq, bhkv)
    group = bhq // bhkv  # (b·H + h) // g == b·Hkv + h // g since g | H
    scale = d ** -0.5 if scale is None else scale
    interpret = kc.resolve_interpret(interpret)

    q = kc.pad_axis_to(q, 1, block_q)
    k = kc.pad_axis_to(k, 1, block_k)
    v = kc.pad_axis_to(v, 1, block_k)
    sq_pad, skv_pad = q.shape[1], k.shape[1]

    grid = (bhq, sq_pad // block_q, skv_pad // block_k)

    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, kv_len=skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, iq, ik, g=group: (h // g, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda h, iq, ik, g=group: (h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, iq, ik: (h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # m
            pltpu.VMEM((block_q,), jnp.float32),        # l
            pltpu.VMEM((block_q, d), jnp.float32),      # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
