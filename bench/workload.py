"""The general traffic generator: one traffic mix file in, one schedule out.

A mix (``bench/traffic/<mix>.json``) names, for each online stream, an
arrival process and two length laws, and for the offline side the job size,
the backlog to keep queued and two length laws.  Arrival processes and
length laws are modules of their own, ``bench/traffic/<kind>.py``, found by
the ``kind`` / ``law`` the mix names.  A new mix made from existing kinds is
a data file only.

Seeding.  What a run sends is split in two:

- the *set* of arrivals and sizes comes from ``shape_seed`` (a number in
  the mix file, the same for every run): how many requests each period of
  the arrival process holds, how long each period is, and the multiset of
  prompt and output lengths;
- the run's ``--seed`` only orders that set: which period comes when,
  where inside its period each arrival falls, which length goes to which
  arrival, and the token ids.

So every seed offers the same work in another order, and run-to-run spread
measures the system, not the luck of the draw.

An arrival module exposes ``periods(params, duration, base_rng)`` →
``[(length_s, n_arrivals, group), ...]`` covering exactly ``duration``
(periods of one ``group`` may swap places), and optionally
``spread(params, length_s, n, rng)`` → offsets inside one period (uniform
when absent).  A length law exposes ``draw(params, n, rng)`` → ints.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

import numpy as np

HERE = Path(__file__).resolve().parent
TRAFFIC_DIR = HERE / 'traffic'

_modules: Dict[str, object] = {}


def kind_module(name: str):
    """``bench/traffic/<name>.py``, imported once."""
    if name not in _modules:
        path = TRAFFIC_DIR / f'{name}.py'
        if not path.is_file():
            raise FileNotFoundError(f'no traffic kind or law {name!r} '
                                    f'({path} is missing)')
        spec = importlib.util.spec_from_file_location(
            f'bench_traffic_{name}', path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _modules[name] = mod
    return _modules[name]


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f'{name}.json'
    if not path.is_file():
        raise FileNotFoundError(f'no traffic mix {name!r} ({path})')
    return json.loads(path.read_text())


@dataclass(frozen=True)
class Arrival:
    """One online request: due ``t`` seconds after its schedule's start."""
    t: float
    stream: str
    prompt: tuple
    max_tokens: int


@dataclass
class Schedule:
    duration: float
    arrivals: List[Arrival] = field(default_factory=list)


def _lengths(law: dict, n: int, rng) -> np.ndarray:
    out = np.asarray(kind_module(law['law']).draw(law, n, rng), np.int64)
    return np.clip(out, law.get('min', 1), law.get('max', np.iinfo(np.int64).max))


def _base_rng(mix: dict, *stream) -> np.random.Generator:
    """The seed-independent generator for one part of the mix."""
    return np.random.default_rng([int(mix.get('shape_seed', 0)), *stream])


def online_schedule(mix: dict, duration: float, seed: int, *,
                    vocab: int, part: int = 0) -> Schedule:
    """Every online stream of ``mix`` over ``duration`` seconds.  ``part``
    keeps the ramp's set apart from the window's."""
    sched = Schedule(duration)
    rng = np.random.default_rng([seed, part, 1])
    for si, st in enumerate(mix.get('online', [])):
        base = _base_rng(mix, part, si)
        arr = kind_module(st['arrival']['kind'])
        periods = arr.periods(st['arrival'], duration, base)
        n = sum(p[1] for p in periods)
        prompts = _lengths(st['prompt'], n, base)
        outputs = _lengths(st['output'], n, base)
        # the seed orders the periods inside each group
        order = list(range(len(periods)))
        for g in {p[2] for p in periods}:
            slots = [i for i, p in enumerate(periods) if p[2] == g]
            for slot, i in zip(slots, rng.permutation(slots)):
                order[slot] = int(i)
        spread = getattr(arr, 'spread', None)
        times, t0 = [], 0.0
        for slot in range(len(periods)):
            length, k, _ = periods[order[slot]]
            offs = (spread(st['arrival'], length, k, rng) if spread
                    else rng.uniform(0.0, length, k))
            times.extend(t0 + np.sort(np.asarray(offs, float)))
            t0 += length
        perm = rng.permutation(n)
        for t, j in zip(times, perm):
            p = int(prompts[j])
            sched.arrivals.append(Arrival(
                float(min(t, duration)), st.get('name', f'online{si}'),
                tuple(rng.integers(1, vocab, p).tolist()),
                int(outputs[j])))
    sched.arrivals.sort(key=lambda a: a.t)
    return sched


class OfflineItems:
    """Offline batch items in a fixed set of sizes, handed out in an order
    the seed picks; endless (the set repeats, reshuffled)."""

    def __init__(self, mix: dict, seed: int, *, vocab: int,
                 pool_size: int = 512):
        spec = mix.get('offline')
        self.spec = spec
        self.job_items = int(spec['job_items']) if spec else 0
        self.min_queued = int(spec['min_queued']) if spec else 0
        if not spec:
            return
        base = _base_rng(mix, 99)
        self._prompts = _lengths(spec['prompt'], pool_size, base)
        self._outputs = _lengths(spec['output'], pool_size, base)
        self._rng = np.random.default_rng([seed, 2])
        self._vocab = vocab
        self._order: List[int] = []

    def __bool__(self) -> bool:
        return bool(self.spec)

    def take(self, n: int) -> List[dict]:
        items = []
        for _ in range(n):
            if not self._order:
                self._order = list(self._rng.permutation(len(self._prompts)))
            j = self._order.pop()
            prompt = self._rng.integers(1, self._vocab,
                                        int(self._prompts[j])).tolist()
            items.append({'prompt': prompt,
                          'max_tokens': int(self._outputs[j])})
        return items
