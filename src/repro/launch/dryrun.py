"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the sharding config is coherent (no mismatched
collectives, fits per-device HBM at compile time) and extracts the roofline
inputs:

- ``compiled.memory_analysis()``  → bytes per device (argument/output/temp);
- ``compiled.cost_analysis()``    → HLO FLOPs + bytes accessed (per device —
  the compiled module is the per-device SPMD program);
- ``compiled.as_text()`` parsed   → collective bytes per device by op kind.

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh single
    python -m repro.launch.dryrun --sweep --out results/dryrun.jsonl
    python -m repro.launch.dryrun --sweep --subprocess   # one proc per cell

Single-cell runs print a JSON record to stdout (the sweep orchestrator and
benchmarks/roofline.py consume these).
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import jax

from repro.configs import ARCHS, SHAPES, cell_supported, get_config
from repro.launch import mesh as meshlib
from repro.models.api import build_model

# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------

def _sds_tree(shapes_tree):
    return shapes_tree  # already ShapeDtypeStructs


def opt_state_sds(param_shapes):
    import jax.numpy as jnp
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return {
        'step': jax.ShapeDtypeStruct((), jnp.int32),
        'mu': jax.tree.map(f32, param_shapes),
        'nu': jax.tree.map(f32, param_shapes),
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             *, microbatches: int = 1, zero1: bool = True,
             rules_variant: str = 'default') -> Dict[str, Any]:
    from repro.distributed import sharding as shd
    from repro.training import optimizer as opt
    from repro.training import train_step as ts

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_supported(cfg, shape)
    rec: Dict[str, Any] = {
        'arch': arch, 'shape': shape_name, 'mesh': mesh_kind,
        'kind': shape.kind, 'microbatches': microbatches,
        'rules_variant': rules_variant,
    }
    if not ok:
        rec.update(status=why)
        return rec

    mesh = meshlib.make_production_mesh(multi_pod=(mesh_kind == 'multi'))
    model = build_model(cfg)
    rules = shd.RULE_VARIANTS.get(rules_variant)
    t0 = time.time()

    try:
        if shape.kind == 'train':
            step_builder, make_sh = ts.make_train_step(
                model, mesh, microbatches=microbatches, zero1=zero1,
                rules=rules)
            jitted = step_builder(shape)
            args = (model.param_shapes(),
                    opt_state_sds(model.param_shapes()),
                    model.input_specs(shape))
            lowered = jitted.lower(*args)
        else:
            jitted, _specs = ts.make_serve_step(model, mesh, shape,
                                                rules=rules)
            args = (model.param_shapes(), model.cache_shapes(shape),
                    model.input_specs(shape))
            lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    except Exception as e:  # a failure here is a bug in our sharding
        rec.update(status='FAILED', error=f'{type(e).__name__}: {e}')
        return rec

    from repro.launch import hlo_analysis as ha
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    costs = ha.analyze(compiled.as_text())

    n_chips = meshlib.chips(mesh)
    # trip-count-corrected per-device figures (cost_analysis counts while
    # bodies once — see hlo_analysis docstring); raw values kept for reference
    flops_dev = costs.flops
    bytes_dev = costs.traffic_bytes
    coll = {'bytes_by_kind': costs.coll_payload,
            'wire_bytes': costs.coll_wire,
            'n_collectives': costs.coll_count}
    hbm_bytes = {
        'argument': int(mem.argument_size_in_bytes),
        'output': int(mem.output_size_in_bytes),
        'temp': int(mem.temp_size_in_bytes),
        'alias': int(mem.alias_size_in_bytes),
        'peak': int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
    }

    # roofline terms (seconds) — per device
    t_comp = flops_dev / meshlib.PEAK_FLOPS_BF16
    t_mem = bytes_dev / meshlib.HBM_BW
    t_coll = coll['wire_bytes'] / meshlib.ICI_BW

    # useful-FLOPs ratio
    if shape.kind == 'train':
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * cfg.active_param_count() * tokens
    elif shape.kind == 'prefill':
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * cfg.active_param_count() * tokens
    else:
        tokens = shape.global_batch  # one token per request
        model_flops = 2 * cfg.active_param_count() * tokens
    hlo_flops_global = flops_dev * n_chips
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    rec.update(
        status='ok',
        chips=n_chips,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        flops_per_device=flops_dev,
        bytes_per_device=bytes_dev,
        raw_cost_analysis={'flops': float(cost.get('flops', 0.0)),
                           'bytes': float(cost.get('bytes accessed', 0.0))},
        hbm=hbm_bytes,
        collectives=coll,
        roofline={
            'compute_s': t_comp, 'memory_s': t_mem, 'collective_s': t_coll,
            'dominant': max((('compute', t_comp), ('memory', t_mem),
                             ('collective', t_coll)), key=lambda kv: kv[1])[0],
        },
        model_flops=model_flops,
        useful_flops_ratio=useful,
    )
    return rec


# ---------------------------------------------------------------------------
# Sweep orchestration
# ---------------------------------------------------------------------------

def all_cells(meshes=('single', 'multi')):
    for arch in ARCHS:
        for shape in SHAPES:
            for mk in meshes:
                yield arch, shape, mk


def sweep(out_path: str, *, use_subprocess: bool, meshes=('single', 'multi'),
          only_missing: bool = True):
    done = set()
    if only_missing and os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get('status') not in (None, 'FAILED'):
                        done.add((r['arch'], r['shape'], r['mesh']))
                except json.JSONDecodeError:
                    pass
    os.makedirs(os.path.dirname(out_path) or '.', exist_ok=True)
    cells = [c for c in all_cells(meshes) if c not in done]
    print(f'[dryrun] {len(cells)} cells to run ({len(done)} cached)',
          flush=True)
    with open(out_path, 'a') as f:
        for i, (arch, shape, mk) in enumerate(cells):
            t0 = time.time()
            if use_subprocess:
                proc = subprocess.run(
                    [sys.executable, '-m', 'repro.launch.dryrun',
                     '--arch', arch, '--shape', shape, '--mesh', mk],
                    capture_output=True, text=True,
                    env={**os.environ,
                         'PYTHONPATH': os.environ.get('PYTHONPATH', 'src')})
                try:
                    rec = json.loads(proc.stdout.strip().splitlines()[-1])
                except Exception:
                    rec = {'arch': arch, 'shape': shape, 'mesh': mk,
                           'status': 'FAILED',
                           'error': (proc.stderr or proc.stdout)[-2000:]}
            else:
                rec = run_cell(arch, shape, mk)
            f.write(json.dumps(rec) + '\n')
            f.flush()
            print(f'[dryrun {i + 1}/{len(cells)}] {arch} × {shape} × {mk}: '
                  f'{rec.get("status")} ({time.time() - t0:.1f}s)', flush=True)


def main():
    # 512 virtual host devices for the production meshes; this must land
    # before jax initializes its backends, which nothing above has done yet
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default=None)
    ap.add_argument('--shape', default=None)
    ap.add_argument('--mesh', default='single', choices=['single', 'multi'])
    ap.add_argument('--sweep', action='store_true')
    ap.add_argument('--subprocess', action='store_true')
    ap.add_argument('--microbatches', type=int, default=1)
    ap.add_argument('--no-zero1', action='store_true')
    ap.add_argument('--rules', default='default',
                    help='sharding-rule variant (see RULE_VARIANTS)')
    ap.add_argument('--out', default='results/dryrun.jsonl')
    args = ap.parse_args()

    if args.sweep:
        sweep(args.out, use_subprocess=args.subprocess)
        return
    assert args.arch and args.shape, '--arch and --shape (or --sweep)'
    rec = run_cell(args.arch, args.shape, args.mesh,
                   microbatches=args.microbatches, zero1=not args.no_zero1,
                   rules_variant=args.rules)
    print(json.dumps(rec))


if __name__ == '__main__':
    main()
