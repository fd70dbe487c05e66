"""Fused unembed + sampling Pallas TPU kernel.

The decode tail the engine's unfused path runs is

    logits = last_hidden @ unembed        # (B, V) to HBM
    token  = argmax(logits)               # separate dispatch (+ host sync)

At production vocab sizes the (B, V) logits tensor is the largest
intermediate of the whole decode step and exists only to be argmax'd.
This kernel tiles the unembed matmul over the vocab axis and carries the
logits→token argmax *reduction* across tiles in VMEM scratch, so logits
never round-trip to HBM: each grid step computes one (B, block_v) score
tile and folds it into a running (best value, best index) pair per row;
the final tile's flush phase writes the (B,) sampled tokens.

Greedy is a plain argmax.  Temperature sampling rides the same reduction
via the Gumbel-max trick (``kernels.common.gumbel_hash_noise``): perturbing
``logits / T`` with counter-hashed Gumbel noise turns exact categorical
sampling into an argmax, which is what makes sampling *fusable* — there is
no normalizer to materialize.

Tie-breaking matches ``jnp.argmax`` bit-for-bit: within a tile the argmax
takes the first occurrence; across tiles a strict ``>`` keeps the earlier
tile's winner, so the composition is the global first-occurrence argmax.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common as kc


def _sample_kernel(seed_ref, last_ref, w_ref, o_ref, best_val_ref,
                   best_idx_ref, *, block_v: int, vocab: int,
                   temperature: float):
    iv = pl.program_id(0)
    nv = pl.num_programs(0)

    @pl.when(iv == 0)
    def _init():
        best_val_ref[...] = jnp.full_like(best_val_ref, kc.NEG_INF)
        best_idx_ref[...] = jnp.zeros_like(best_idx_ref)

    last = last_ref[...].astype(jnp.float32)          # (B, D)
    w = w_ref[...].astype(jnp.float32)                # (D, block_v)
    s = jax.lax.dot_general(last, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    col = kc.block_positions(iv, block_v, s.shape, 1)  # global vocab ids
    if temperature > 0.0:
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = s / temperature + kc.gumbel_hash_noise(seed_ref[0], row, col)
    # vocab padding tiles (and the ragged last tile) must never win
    s = jnp.where(col < vocab, s, kc.NEG_INF)

    tile_max = jnp.max(s, axis=1)
    tile_arg = jnp.argmax(s, axis=1).astype(jnp.int32) + iv * block_v
    better = tile_max > best_val_ref[...]   # strict: first occurrence wins
    best_idx_ref[...] = jnp.where(better, tile_arg, best_idx_ref[...])
    best_val_ref[...] = jnp.where(better, tile_max, best_val_ref[...])

    @pl.when(iv == nv - 1)
    def _flush():
        o_ref[...] = best_idx_ref[...][:, None]


@functools.partial(jax.jit,
                   static_argnames=('temperature', 'block_v', 'interpret'))
def unembed_sample_pallas(last, unembed, seed, *, temperature: float = 0.0,
                          block_v: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """last: (B, D) final-norm hidden; unembed: (D, V); seed: (1,) int32.

    Returns (B,) int32 sampled tokens.  ``temperature`` is static (the
    engine config pins it); the seed is a traced array so per-step reseeds
    never recompile.
    """
    b, d = last.shape
    v = unembed.shape[1]
    bv = block_v or kc.pick_block(v, 1024, align=kc.LANES)
    wp = kc.pad_axis_to(unembed, 1, bv)
    nv = wp.shape[1] // bv
    interpret = kc.resolve_interpret(interpret)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nv,),
        in_specs=[
            pl.BlockSpec((b, d), lambda iv, sd: (0, 0)),
            pl.BlockSpec((d, bv), lambda iv, sd: (0, iv)),
        ],
        out_specs=pl.BlockSpec((b, 1), lambda iv, sd: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((b,), jnp.float32),
            pltpu.VMEM((b,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_sample_kernel, block_v=bv, vocab=v,
                          temperature=float(temperature)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
    )(jnp.asarray(seed, jnp.int32), last, wp)
    return out[:, 0]
