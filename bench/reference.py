"""Plain reference forward for the served models, and its int8 control.

Each model's reference is its architecture's: ``Reference`` of
``bench/archs/<architectures[0]>.py`` (``bench/archs/__init__.py`` says
what such a module gives).  Each is written from the published
architecture, in ``jax.numpy`` and float32 under
``default_matmul_precision('highest')``, importing nothing of the program,
with weights made from the seed by the family's law (``_decoder`` gives the
one the dense decoders share).

The comparison (``Reference(hf, seed).gaps``): for a prompt and the tokens
the system served after it, the reference runs once over prompt + served
tokens and reads, at each served position, how far the served token's
logit lies below the reference's best.  Greedy serving gives 0 up to
rounding.  The control puts the same forward in the program's place with
every weight matmul in int8 (weights per output channel, activations per
token, symmetric), and reads the gap of the token it puts first.
"""
from __future__ import annotations

import archs


def Reference(hf: dict, seed: int, mesh=None):
    """One model's reference from its published config and the seed, with
    its weights laid over ``mesh`` (the node's) where one is given."""
    return archs.of(hf).Reference(hf, seed, mesh=mesh)
