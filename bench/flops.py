"""Operations and bytes the algorithm needs, from shapes.

Counted from what the work requires, not from what a kernel or a padded
dispatch touches: real rows only, and attention over each row's live
tokens.  A step that skips padding or dead pages therefore reads closer to
its peak, and none can read above it.

A model here is the served config (``bench/configs``, Hugging Face keys).
Its architecture's module, ``bench/archs/<architectures[0]>.py``, gives
its parameters, its output head and the attention work of a step and of
each paged-decode call; the steps are summed from them here.
"""
from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import archs


def layer_params(hf: dict) -> int:
    """Matrix parameters of one layer (norm weights left out); the mean
    over the layers where they differ."""
    return body_params(hf) // hf['num_hidden_layers']


def body_params(hf: dict) -> int:
    """Non-embedding matrix parameters a token passes through."""
    return archs.of(hf).body_params(hf)


def attn_flops_per_key(hf: dict) -> int:
    """QK^T and PV for one query against one key, summed over the layers
    that attend."""
    return archs.of(hf).attn_flops(hf, (), (1,))


def unembed_flops(hf: dict) -> int:
    return archs.of(hf).unembed_flops(hf)


def decode_step_flops(hf: dict, live: Sequence[int]) -> int:
    """One pure-decode step: one token per real row; ``live`` holds each
    row's attended tokens (its context, the new token included)."""
    rows = len(live)
    return (rows * (2 * body_params(hf) + unembed_flops(hf))
            + archs.of(hf).attn_flops(hf, (), live))


def mixed_step_flops(hf: dict, prefill: Iterable[Tuple[int, int]],
                     decode_live: Sequence[int]) -> int:
    """One mixed dispatch: prefill rows ``(start, length)`` attend
    causally to their context; decode rows as in a decode step.  Logits
    are taken once per real row."""
    prefill = list(prefill)
    toks = sum(n for _, n in prefill) + len(decode_live)
    rows = len(prefill) + len(decode_live)
    return (2 * body_params(hf) * toks + unembed_flops(hf) * rows
            + archs.of(hf).attn_flops(hf, prefill, decode_live))


def paged_decode_calls(hf: dict, live: Sequence[int]) -> List[Tuple[int, int]]:
    """(flops, bytes) of every paged-decode attention call of one
    pure-decode step over real rows with ``live`` tokens each: q in, out
    back, and the K and V of the tokens each call attends to (not the
    pages the kernel walks)."""
    return archs.of(hf).paged_decode_calls(hf, live)


def paged_decode_call(hf: dict, live: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of the step's largest paged-decode call: one layer's,
    where every layer attends alike."""
    return max(paged_decode_calls(hf, live))
