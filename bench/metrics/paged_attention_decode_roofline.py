"""Kernels: the Pallas paged-decode attention kernel's share of its
roofline in the traced stretch, in %.

Ideal time: for every kernel call of every pure-decode step of either
engine (``flops.paged_decode_calls``, from the model's architecture), the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth (q, out, and the K and V of the tokens the call attends to, not
the pages the kernel walks).  Device time: the summed duration of the
kernel's ops in the trace.  Moves ``tpot_p90_ms``."""
import sys
from collections import Counter

import devtrace
import flops

# the kernel's op in the trace: ``%paged_attention.<n> = ... custom-call``
KERNEL = '%paged_attention'


def is_kernel(op_name: str) -> bool:
    return op_name == KERNEL or op_name.startswith(KERNEL + '.')


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    calls, device_s = devtrace.kernel_time(run.trace, is_kernel)
    steps = run.steps_in(*run.traced, kind='decode')
    if not calls or not steps:
        return None
    ideal, mem_bound = 0.0, 0
    expected = 0
    for s in steps:
        step_calls = flops.paged_decode_calls(run.engines[s.engine], s.live)
        for (f, b), n in Counter(step_calls).items():   # alike calls at once
            tf = f / run.peaks['bf16_flops_per_s']
            tb = b / run.peaks['hbm_bytes_per_s']
            ideal += n * max(tf, tb)
            mem_bound += n * (tb >= tf)
            expected += n
    print(f'paged_attention_decode_roofline: {calls} kernel calls in the trace, '
          f'{expected} from {len(steps)} decode steps; bound by memory in '
          f'{mem_bound} of {expected}', file=sys.stderr)
    return 100.0 * ideal / device_s
