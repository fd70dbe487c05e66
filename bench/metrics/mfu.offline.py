"""Model step: operations the offline engines' steps in the window need
(``flops.mixed_step_flops`` for mixed steps: real prefill tokens and
decode rows, not the padding of the fixed dispatch shape, causal attention
over the live context; ``flops.decode_step_flops`` for pure-decode steps)
over their summed host time times the chip's bf16 peak, in %.  A faster
offline step is a shorter wait for an online request that arrives during
it: moves ``ttft_p90_ms``."""
import flops


def step_flops(hf, s):
    if s.kind == 'mixed':
        return flops.mixed_step_flops(hf, s.prefill, s.live)
    return flops.decode_step_flops(hf, s.live)


def read(run):
    steps = run.steps_in(run.w0, run.w1, klass='offline')
    if not steps or run.peaks is None:
        return None
    work = sum(step_flops(run.engines[s.engine], s) for s in steps)
    t = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * work / (t * run.peaks['bf16_flops_per_s'])
