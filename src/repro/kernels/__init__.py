# Pallas kernel layer.  Every kernel package (flash_attention,
# paged_attention, rwkv6) is <name>/kernel.py + ops.py + ref.py; import the
# public entry points from the ops modules, e.g.
#
#     from repro.kernels.flash_attention.ops import flash_attention
#     from repro.kernels.paged_attention.ops import (paged_attention,
#                                                    paged_attention_decode)
#     from repro.kernels.rwkv6.ops import wkv6
#
# The ops modules are deliberately not imported here: non-kernel consumers
# of repro.kernels.common (e.g. the models, on every import path) must not
# pay the Pallas ops import cost, and the function names shadow their
# subpackage names, so package-level function re-exports are an
# import-order hazard.  Shared machinery lives in repro.kernels.common.
from repro.kernels.common import resolve_interpret  # noqa: F401
