"""InternLM2 (``InternLM2ForCausalLM``, hf ``internlm/internlm2-1_8b``):
the dense decoder of ``_decoder`` without qk-norm, with a separate output
head.  ``rope_scaling`` is left out: every sequence the benchmark serves
stays far below ``max_position_embeddings``."""
import functools

from archs import _decoder
from archs._decoder import (attn_flops, body_params,  # noqa: F401
                            paged_decode_calls, unembed_flops)

program_config = functools.partial(_decoder.program_config, qk_norm=False)
Reference = functools.partial(_decoder.Reference, qk_norm=False)
