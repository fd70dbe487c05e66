"""Readings that the limits of ``correct`` are set from.

    python3 bench/calibrate.py --workload qwen_on.chat_burst \\
        --seconds 15 --seeds 101 102 103 ...

For each seed, in one process: one run of the cell at its own load with a
short window (``harness.run_cell``), then the reference over the same
sample of served requests, and the control (the reference with every
weight matmul in int8, :mod:`reference`) in the program's place.  Prints
one JSON line per seed and, last, per compared number the largest
program reading (the lower reading) and the smallest control reading
(the upper reading).  The control's readings are held to the limits
through the harness's own comparison (``run_cell(control=True)``):
``control_correct`` has to be false on every seed, and the script exits
1 where it is not.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import io           # noqa: E402
import json         # noqa: E402
import sys          # noqa: E402
from pathlib import Path    # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seconds', type=float, default=15.0)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / 'src'))
    import harness
    import jax
    if jax.devices()[0].platform != 'tpu':
        print('calibrate: needs a TPU', file=sys.stderr)
        return 2
    limits = harness.load_cell(args.workload).config.get('limits', {})
    program, control, passed = {}, {}, []
    for seed in args.seeds:
        buf = io.StringIO()
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=time.monotonic(), out=buf,
                               control=True)
        row = {'seed': seed, 'control_correct': res['correct'],
               'program_within_limits': all(
                   v <= limits[k] for k, v in res['readings'].items()
                   if k in limits)}
        if res['correct']:
            passed.append(seed)
        for k, v in res['readings'].items():
            row[k] = v
            program.setdefault(k, []).append(v)
        for k, v in res['control'].items():
            row[k + '.control'] = v
            control.setdefault(k, []).append(v)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        'workload': args.workload, 'seeds': args.seeds,
        'lower': {k: max(v) for k, v in program.items()},
        'upper': {k: min(v) for k, v in control.items()},
        'program': program, 'control': control,
        'control_correct_on': passed}))
    return 1 if passed else 0


if __name__ == '__main__':
    sys.exit(main())
