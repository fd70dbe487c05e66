"""Model step: operations the online engine's pure-decode steps in the
window need (``flops.decode_step_flops``: real rows, attention over live
tokens) over their summed host time times the node's bf16 peak (one
chip's times the chips the node spans), in %.  Moves ``tpot_p90_ms``."""
import flops


def read(run):
    steps = run.steps_in(run.w0, run.w1, klass='online', kind='decode')
    if not steps or run.peaks is None:
        return None
    work = sum(flops.decode_step_flops(run.engines[s.engine], s.live)
               for s in steps)
    t = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * work / (t * run.peaks['bf16_flops_per_s'] * run.chips)
