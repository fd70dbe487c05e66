"""The benchmark's CPU tests: ``python -m pytest bench/tests -q``."""
import copy
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / 'src'))
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

SMALL = {'hidden_size': 64, 'intermediate_size': 128,
         'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 16,
         'num_hidden_layers': 2, 'vocab_size': 512}


def small_model(entry: dict) -> dict:
    e = copy.deepcopy(entry)
    e['config'].update(SMALL)
    return e


def shrink(cfg: dict, mix: dict):
    """A cell at CPU size: reduced widths, a small pool, short requests."""
    cfg = copy.deepcopy(cfg)
    mix = copy.deepcopy(mix)
    cfg['online'] = small_model(cfg['online'])
    cfg['offline'] = [small_model(o) for o in cfg['offline']]
    cfg['page_size'] = 4
    cfg['node'] = {'n_handles': 24, 'pages_per_handle': 8, 'max_seq': 96,
                   'prefill_chunk': 16, 'max_prefill_reqs': 4}
    parts = list(mix.get('online', []))
    if mix.get('offline'):
        parts.append(mix['offline'])
    for p in parts:
        p['prompt'].update(median=24, min=4, max=48)
        if p['prompt']['law'] == 'lognormal':
            p['prompt']['sigma'] = min(p['prompt']['sigma'], 0.5)
        p['output'] = {'law': 'uniform', 'low': 2, 'high': 12, 'min': 2,
                       'max': 12}
    return cfg, mix
