"""Operations and bytes the algorithm needs, from shapes.

Counted from what the work requires, not from what a kernel or a padded
dispatch touches: real rows only, and attention over each row's live
tokens.  A step that skips padding or dead pages therefore reads closer to
its peak, and none can read above it.

A model here is the served config (``bench/configs``, Hugging Face keys).
"""
from __future__ import annotations

from typing import Iterable, Sequence, Tuple

BF16 = 2


def dims(hf: dict) -> dict:
    d, h = hf['hidden_size'], hf['num_attention_heads']
    hd = hf.get('head_dim') or d // h
    return {'d': d, 'f': hf['intermediate_size'], 'h': h,
            'hkv': hf['num_key_value_heads'], 'hd': hd,
            'L': hf['num_hidden_layers'], 'V': hf['vocab_size']}


def layer_params(hf: dict) -> int:
    """Matrix parameters of one layer (norm weights left out)."""
    m = dims(hf)
    q, kv = m['h'] * m['hd'], m['hkv'] * m['hd']
    return m['d'] * (q + 2 * kv) + q * m['d'] + 3 * m['d'] * m['f']


def body_params(hf: dict) -> int:
    """Non-embedding matrix parameters: every layer's."""
    return dims(hf)['L'] * layer_params(hf)


def attn_flops_per_key(hf: dict) -> int:
    """QK^T and PV for one query against one key, summed over layers."""
    m = dims(hf)
    return 4 * m['h'] * m['hd'] * m['L']


def unembed_flops(hf: dict) -> int:
    m = dims(hf)
    return 2 * m['d'] * m['V']


def decode_step_flops(hf: dict, live: Sequence[int]) -> int:
    """One pure-decode step: one token per real row; ``live`` holds each
    row's attended tokens (its context, the new token included)."""
    rows = len(live)
    return (rows * (2 * body_params(hf) + unembed_flops(hf))
            + attn_flops_per_key(hf) * sum(live))


def mixed_step_flops(hf: dict, prefill: Iterable[Tuple[int, int]],
                     decode_live: Sequence[int]) -> int:
    """One mixed dispatch: prefill rows ``(start, length)`` attend
    causally to their context; decode rows as in a decode step.  Logits
    are taken once per real row."""
    prefill = list(prefill)
    toks = sum(n for _, n in prefill) + len(decode_live)
    rows = len(prefill) + len(decode_live)
    keys = sum(n * start + n * (n + 1) // 2 for start, n in prefill)
    keys += sum(decode_live)
    return (2 * body_params(hf) * toks + unembed_flops(hf) * rows
            + attn_flops_per_key(hf) * keys)


def paged_decode_call(hf: dict, live: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of one paged-decode attention call (one layer) over
    real rows with ``live`` tokens each: q in, out back, and the K and V
    of the live tokens (not the pages the kernel walks)."""
    m = dims(hf)
    rows = len(live)
    flops = 4 * m['h'] * m['hd'] * sum(live)
    qo = 2 * rows * m['h'] * m['hd'] * BF16
    kv = 2 * sum(live) * m['hkv'] * m['hd'] * BF16
    return flops, qo + kv
