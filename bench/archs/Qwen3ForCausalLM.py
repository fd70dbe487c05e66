"""Qwen3 (``Qwen3ForCausalLM``, hf ``Qwen/Qwen3-0.6B``): the dense decoder
of ``_decoder`` with RMSNorm on each head of q and k before RoPE; the
published models tie the input and output embeddings."""
import functools

from archs import _decoder
from archs._decoder import (attn_flops, body_params,  # noqa: F401
                            paged_decode_calls, unembed_flops)

program_config = functools.partial(_decoder.program_config, qk_norm=True)
Reference = functools.partial(_decoder.Reference, qk_norm=True)
