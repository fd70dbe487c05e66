"""One module per served architecture, found by the name a model's config
gives first in ``architectures``: ``bench/archs/<name>.py``.

A configuration whose models name an architecture brings that module
beside its config file; no other file of the benchmark changes.  The
module imports nothing of the program and gives:

- ``program_config(hf, page_size) -> dict``: the keyword arguments of the
  program's ``repro.configs.ModelConfig`` (its ``family`` included, its
  ``name`` left to the harness), from the model's Hugging Face config;
- ``Reference(hf, seed, mesh=None)``, whose ``gaps(prompt, served, *,
  control=False)`` reads a served sequence against a plain float32 forward
  under ``default_matmul_precision('highest')``, with weights made from the
  seed by the family's law, and the W8A8 control (see
  ``bench/reference.py``); with a ``jax.sharding.Mesh`` (the node's, where
  the config declares one) the weights are laid over its devices, so a
  model that fits the mesh and not one chip can be read, with the same
  bits in the weights;
- the counts the per-layer readers need (``bench/flops.py``), each a
  function of the Hugging Face config, each an exact integer:
  ``body_params(hf)``: matrix parameters a token passes through, outside
  the embedding and the output head (for experts, the active ones);
  ``unembed_flops(hf)``: the output head's operations for one token;
  ``attn_flops(hf, prefill, decode_live)``: QK^T and PV of one step, over
  the layers that attend, where prefill rows ``(start, n)`` attend
  causally to their context and decode rows to their ``live`` tokens (a
  windowed layer to at most its window);
  ``paged_decode_calls(hf, live) -> [(flops, bytes), ...]``: one entry for
  each call of the paged decode kernel in a pure-decode step over rows
  with ``live`` tokens each: q in, out back, and the K and V bytes of the
  tokens the call attends to.

``_decoder`` holds the pieces an architecture builds on: RMSNorm,
rotate-half RoPE, GQA attention, SwiGLU, the W8A8 matmul, the weights law,
the padded comparison, the keys a step attends to (with or without a
window), one paged-decode call's work, and the dense decoder's forward and
counts.
"""
from __future__ import annotations

import importlib
from pathlib import Path

HERE = Path(__file__).resolve().parent


def module(name: str):
    """The module of architecture ``name``."""
    if not (name.isidentifier() and (HERE / f'{name}.py').is_file()):
        raise KeyError(f'no module for architecture {name!r}: add '
                       f'bench/archs/{name}.py (see bench/archs/__init__.py '
                       f'for what it gives)')
    return importlib.import_module(f'{__name__}.{name}')


def of(hf: dict):
    """The module of the architecture a Hugging Face config names."""
    return module(hf['architectures'][0])
