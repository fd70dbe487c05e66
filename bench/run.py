"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload qwen_on.chat_burst --seed 7 \\
        --seconds 45 --trace 0

Prints its findings on standard error and, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, last, ``checks`` (each compared
number beside its limit).  Exits non-zero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse     # noqa: E402
import sys          # noqa: E402
from pathlib import Path    # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / 'src'))
    import harness
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu' or len(devices) < cell.chips:
        print(f'bench: {args.workload} needs {cell.chips} TPU chip(s); JAX '
              f'found {len(devices)} {devices[0].platform} device(s)',
              file=sys.stderr)
        return 2
    harness.run_cell(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=T_START)
    return 0


if __name__ == '__main__':
    sys.exit(main())
