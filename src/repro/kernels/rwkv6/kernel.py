"""Chunked WKV6 (RWKV-6 'Finch') linear-attention Pallas TPU kernel.

The recurrence  S_t = diag(w_t)·S_{t-1} + k_tᵀ v_t,  y_t = r_t·(S_{t-1} +
diag(u)·k_tᵀv_t)  is evaluated chunk-parallel: within a chunk of c tokens
everything is (c×K)·(K×c) MXU matmuls against cumulative-decay-weighted
r/k; the (K, V) state carries across chunks in VMEM scratch.  This is the
TPU-native adaptation of the CUDA wkv kernels: instead of one thread per
(b, h) scanning tokens serially, the chunk dimension feeds the 128×128 MXU
and only the O(T/c) chunk boundary is sequential.

Grid ``(B, H, n_chunks)`` — chunks innermost/sequential ('arbitrary');
state scratch (K, V) f32.  Padding tokens must carry w=1, k=0, r=0 (decay
no-op, no state contribution) — the wrapper guarantees this.  Padding goes
through :mod:`repro.kernels.common` (wkv6 has no softmax, so the
online-softmax helpers don't apply here).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as kc


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 y_ref, sout_ref, state_ref, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0].astype(jnp.float32)            # (c, K)
    k = k_ref[0, :, 0].astype(jnp.float32)
    v = v_ref[0, :, 0].astype(jnp.float32)            # (c, V)
    w = w_ref[0, :, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                  # (K,)

    logw = jnp.log(jnp.maximum(w, 1e-30))
    logA = jnp.cumsum(logw, axis=0)                   # inclusive (c, K)
    a_end = jnp.exp(logA[-1])                         # (K,)
    r_dec = r * jnp.exp(logA - logw)                  # r_t ∘ A_{t-1} (≤ A_0)
    k_end = k * jnp.exp(logA[-1:] - logA)             # (A_T/A_i) ∘ k_i (≤ 1)
    # intra-chunk scores in midpoint-normalized decay space: the factored
    # form r·A_{t-1} × k/A_s overflows f32 when the in-chunk decay range
    # exceeds ~85 nats; normalizing both sides by A_{mid} bounds each factor
    # by exp(range/2) while every causal product stays ≤ 1
    mid = logA[chunk // 2]
    r_dec_m = r * jnp.exp(logA - logw - mid[None, :])
    k_inc_m = k * jnp.exp(mid[None, :] - logA)

    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    scores = dot(r_dec_m, k_inc_m, (((1,), (1,)), ((), ())))  # (c, c)
    ti = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    si = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(ti > si, scores, 0.0)                  # strictly causal
    y = dot(scores, v, (((1,), (0,)), ((), ())))              # intra
    y += jnp.sum(r * (u[None, :] * k), axis=1, keepdims=True) * v   # diag
    state = state_ref[...]
    y += dot(r_dec, state, (((1,), (0,)), ((), ())))          # inter

    state_ref[...] = (a_end[:, None] * state
                      + dot(k_end, v, (((0,), (0,)), ((), ()))))
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _flush():
        sout_ref[0, 0] = state_ref[...].astype(sout_ref.dtype)


def wkv6_bthk(r, k, v, w, u, state, *, chunk: int = 64,
              interpret: Optional[bool] = None):
    """r/k/v/w: (B, T, H, K); u: (H, K); state: (B, H, K, V) f32.

    Returns (y (B, T, H, V), state_out (B, H, K, V)).
    """
    b, t, h, dk = r.shape
    dv = v.shape[-1]
    interpret = kc.resolve_interpret(interpret)
    t_pad = kc.round_up(t, chunk)
    if t_pad != t:
        r = kc.pad_axis_to(r, 1, chunk)
        k = kc.pad_axis_to(k, 1, chunk)
        v = kc.pad_axis_to(v, 1, chunk)
        w = kc.pad_axis_to(w, 1, chunk, value=1.0)    # decay no-op

    grid = (b, h, t_pad // chunk)
    io_spec = lambda: pl.BlockSpec((1, chunk, 1, dk),
                                   lambda ib, ih, ic: (ib, ic, ih, 0))
    y, sout = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            io_spec(), io_spec(),
            pl.BlockSpec((1, chunk, 1, dv), lambda ib, ih, ic: (ib, ic, ih, 0)),
            io_spec(),
            pl.BlockSpec((1, dk), lambda ib, ih, ic: (ih, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, 1, dv), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, t_pad, h, dv), r.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
    )(r, k, v, w, u, state)
    return y[:, :t], sout
