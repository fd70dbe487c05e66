"""Shared kernel toolkit.

It centralizes the machinery all three Pallas kernels (flash, paged,
wkv6) previously re-implemented:

- TPU-lane-aligned block/tile-size selection and padding
  (:func:`pick_block`, :func:`pad_axis_to`);
- the online-softmax running max/denominator update carried across the
  sequential grid axis (:func:`online_softmax_init` /
  :func:`online_softmax_update` / :func:`online_softmax_finalize`);
- causal and length ("quarantine") masking on score blocks
  (:func:`mask_block_scores`);
- automatic interpret-mode fallback off-TPU (:func:`resolve_interpret`) so
  the parity suite runs everywhere.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    'NEG_INF', 'LANES', 'SUBLANES',
    'resolve_interpret',
    'ceil_div', 'round_up', 'pick_block', 'pad_axis_to',
    'online_softmax_init', 'online_softmax_update', 'online_softmax_finalize',
    'block_positions', 'mask_block_scores',
    'hash_u32', 'gumbel_hash_noise',
]

# Softmax mask fill value: large-negative but finite in f32, so a fully
# masked row underflows exp() to 0 instead of producing NaN via inf - inf.
NEG_INF = -1e30

# TPU register tiling: last dim is always 128 lanes; the f32 sublane count
# is 8 (doubles for bf16 / quadruples for int8 — see the Pallas guide).
LANES = 128
SUBLANES = 8


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret`` tri-state: None → auto.

    Mosaic kernels only compile for TPU backends; everywhere else (the CPU
    parity/CI suites, GPU dev boxes) the same kernel runs under the Pallas
    interpreter, which lowers to plain HLO.  Passing an explicit bool always
    wins — tests pin ``interpret=True`` so they are hermetic.
    """
    if interpret is None:
        return jax.default_backend() != 'tpu'
    return interpret


# ---------------------------------------------------------------------------
# Block / tile selection and padding
# ---------------------------------------------------------------------------

def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, multiple: int) -> int:
    return ceil_div(x, multiple) * multiple


def pick_block(dim: int, preferred: int, *, align: int = SUBLANES) -> int:
    """Block size for a ``dim``-long *sequence* axis: ``preferred``, shrunk
    for short axes but always a multiple of ``align`` so tiles stay
    sublane-aligned (the last/lane dim of a tile is the head dim and is
    fixed by the caller, so the default alignment here is the f32 sublane
    count).

    A 1024-token axis at preferred 128 → 128; a 50-token axis → 56 (one
    near-fit block beats a mostly-padded 128); a 300-token axis at
    preferred 512 → 304.
    """
    assert preferred % align == 0, (preferred, align)
    if dim >= preferred:
        return preferred
    return max(align, min(preferred, round_up(dim, align)))


def pad_axis_to(x, axis: int, multiple: int, *, value=0):
    """Zero-pad (or ``value``-pad) one axis of ``x`` up to a multiple.

    Returns ``x`` unchanged when already aligned — the common case at
    production shapes, so no copy is inserted.
    """
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


# ---------------------------------------------------------------------------
# Online softmax (the running-max/denominator state all attention kernels
# carry across their sequential KV/page grid axis)
# ---------------------------------------------------------------------------

def online_softmax_init(m_ref, l_ref, acc_ref) -> None:
    """Reset the VMEM scratch carried across the sequential grid axis."""
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def online_softmax_update(s, v, m_prev, l_prev, acc_prev):
    """One online-softmax step over a masked score block.

    s: (rows, cols) f32 scores (masked entries at NEG_INF); v: (cols, D).
    Returns the rescaled ``(m_new, l_new, acc_new)`` running state.  Fully
    masked rows are safe: ``exp(NEG_INF - m)`` underflows to 0.
    """
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_new = (acc_prev * alpha[:, None]
               + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32))
    return m_new, l_new, acc_new


def online_softmax_finalize(acc, l):
    """acc / l with fully-masked rows (l == 0) mapped to 0, not NaN."""
    safe = jnp.where(l == 0.0, 1.0, l)
    return acc / safe[:, None]


# ---------------------------------------------------------------------------
# Counter-based sampling noise (shared by the fused sampling kernel and its
# jnp reference so kernel-vs-ref parity is bit-identical)
# ---------------------------------------------------------------------------

def hash_u32(x):
    """Stateless u32 avalanche hash (splitmix-style finalizer).

    Pure element-wise integer ops, so it lowers identically inside a Pallas
    kernel and in plain jnp — the property the fused-sampling parity suite
    relies on.  Input is cast to uint32; multiplication wraps mod 2**32.
    """
    x = jnp.asarray(x).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def gumbel_hash_noise(seed, rows, cols):
    """Deterministic Gumbel(0, 1) noise per (row, col) counter.

    ``argmax(logits / T + gumbel)`` is an exact sample from
    ``softmax(logits / T)`` (the Gumbel-max trick), so the fused sampling
    kernel can carry temperature sampling as a pure argmax reduction — no
    cumulative-sum search, no logits round-trip.  The noise is a counter
    hash (seed, row, col), not a stream: any tile of the (B, V) grid can be
    generated independently inside its kernel block and matches the jnp
    reference bit-for-bit.
    """
    seed = jnp.asarray(seed).astype(jnp.uint32)
    h = hash_u32(seed ^ (jnp.asarray(rows).astype(jnp.uint32)
                         * jnp.uint32(0x9E3779B9)))
    bits = hash_u32(h ^ jnp.asarray(cols).astype(jnp.uint32))
    # top 24 bits → uniform on the open interval (0, 1): representable
    # exactly in f32, never 0 or 1, so the double log below stays finite
    u = ((bits >> jnp.uint32(8)).astype(jnp.float32)
         * jnp.float32(2.0 ** -24) + jnp.float32(2.0 ** -25))
    return -jnp.log(-jnp.log(u))


# ---------------------------------------------------------------------------
# Masking (causal + length/quarantine)
# ---------------------------------------------------------------------------

def block_positions(block_index, block_size: int, shape, dim: int):
    """Absolute positions of a tile's rows/cols: block offset + iota."""
    return block_index * block_size + jax.lax.broadcasted_iota(
        jnp.int32, shape, dim)


def mask_block_scores(s, *, q_pos=None, k_pos=None, causal: bool = False,
                      kv_len=None):
    """Apply causal and/or valid-length masking to a score block.

    ``kv_len`` bounds valid KV positions — this is the quarantine contract:
    tokens past a request's length (including garbage streamed from the
    always-mapped quarantine page) are forced to NEG_INF so they cannot
    contribute, which is what makes page reclamation harmless for healthy
    requests (paper §5).
    """
    mask = None
    if kv_len is not None:
        assert k_pos is not None
        mask = k_pos < kv_len
    if causal:
        assert q_pos is not None and k_pos is not None
        cmask = q_pos >= k_pos
        mask = cmask if mask is None else (mask & cmask)
    if mask is None:
        return s
    return jnp.where(mask, s, NEG_INF)
