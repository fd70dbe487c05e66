"""Reduction of a profiler trace to device time.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` and keeps:

- the device's op events: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, as (name, start, end) in nanoseconds.  An event's name there is
  the whole HLO instruction; :func:`op_name` keeps its own name
  (``%paged_attention.9``), :func:`op_label` adds its opcode and shape;
- the benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
  that start with one of :data:`HOST_SPANS`), on the same clock;
- the traced window, between the ``bench.window`` marks.

Busy time is the union of op intervals inside the window; idle is the rest.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

OPS_LINE = 'XLA Ops'
WINDOW_MARK = 'bench.window'
HOST_SPANS = ('engine.step', 'runtime.tick', 'driver.', 'bench.')
IDLE_OUTSIDE_SPANS = 'event loop: front end, generator, SSE'


CONTAINERS = (' while(', ' conditional(', ' call(')


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[8,128] fusion(...)`` → ``%fusion.12``."""
    return event_name.split(' = ', 1)[0]


def op_label(event_name: str) -> str:
    """The op's name, result shape and opcode, operands left out."""
    head, _, rest = event_name.partition(' = ')
    shape, _, call = rest.partition(' ')
    return f'{head} {shape[:60]} {call.split("(", 1)[0]}'.strip()


@dataclass
class Trace:
    # per device: names (list), starts / ends (int64 ns arrays)
    devices: Dict[str, Tuple[List[str], np.ndarray, np.ndarray]]
    host: List[Tuple[str, int, int]] = field(default_factory=list)
    window: Tuple[int, int] = (0, 0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, device: Optional[str] = None):
        """(names, starts, ends) of one device, or of all together."""
        keys = [device] if device else sorted(self.devices)
        names = [n for k in keys for n in self.devices[k][0]]
        starts = np.concatenate([self.devices[k][1] for k in keys])
        ends = np.concatenate([self.devices[k][2] for k in keys])
        return names, starts, ends


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f'no .xplane.pb under {log_dir}')
    return max(files, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host, marks = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith('/device:TPU:'):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                names, s, e = [], [], []
                for ev in line.events:
                    names.append(ev.name)
                    s.append(ev.start_ns)
                    e.append(ev.end_ns)
                devices[plane.name] = (names, np.asarray(s, np.int64),
                                       np.asarray(e, np.int64))
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_MARK:
                        marks.append(int(ev.start_ns))
                    elif ev.name.startswith(HOST_SPANS):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.end_ns)))
    if not devices:
        raise ValueError(f'{path}: no {OPS_LINE!r} line on any TPU plane')
    if len(marks) < 2:
        raise ValueError(f'{path}: the {WINDOW_MARK!r} marks are missing')
    return Trace(devices, sorted(host, key=lambda h: h[1]),
                 (min(marks), max(marks)))


def _union(starts, ends, lo, hi) -> List[Tuple[int, int]]:
    """Merged intervals of [starts, ends) clipped to [lo, hi)."""
    order = np.argsort(starts, kind='stable')
    out: List[List[int]] = []
    for s, e in zip(starts[order], ends[order]):
        s, e = max(int(s), lo), min(int(e), hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices traced."""
    lo, hi = trace.window
    per = [sum(e - s for s, e in _union(st, en, lo, hi)) * 1e-9
           for _, st, en in trace.devices.values()]
    return float(np.mean(per))


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def top_ops(trace: Trace, n: int = 10) -> List[list]:
    """The device ops that took most time in the window, by op label,
    averaged over the devices traced (as :func:`busy_s`); a loop or call
    op, whose time is its body's, is left out."""
    lo, hi = trace.window
    tot: Dict[str, int] = defaultdict(int)
    names, st, en = trace.ops()
    for name, s, e in zip(names, st, en):
        s, e = max(int(s), lo), min(int(e), hi)
        if e > s and not any(c in name for c in CONTAINERS):
            tot[op_label(name)] += e - s
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / len(trace.devices)] for k, v in best]


def idle_by_host(trace: Trace, n: int = 10) -> List[list]:
    """Idle device time in the window, by the innermost benchmark span the
    host was in at the middle of each gap (first device only: on a node
    that spans several chips one SPMD program runs on all of them, which
    step in lockstep)."""
    lo, hi = trace.window
    first = sorted(trace.devices)[0]
    _, st, en = trace.devices[first]
    busy = _union(st, en, lo, hi)
    edges = [lo] + [x for b in busy for x in b] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = trace.host
    starts = np.asarray([h[1] for h in spans], np.int64)
    tot: Dict[str, int] = defaultdict(int)
    for a, b in gaps:
        mid = (a + b) // 2
        k = int(np.searchsorted(starts, mid, side='right'))
        label, width = IDLE_OUTSIDE_SPANS, None
        # spans are short and few overlap: walk back over candidates
        for name, s, e in reversed(spans[max(0, k - 64):k]):
            if s <= mid < e and (width is None or e - s < width):
                label, width = name, e - s
        tot[label] += b - a
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def kernel_time(trace: Trace, match) -> Tuple[int, float]:
    """(calls, device seconds) of the ops whose own name (:func:`op_name`)
    ``match`` accepts."""
    lo, hi = trace.window
    n, tot = 0, 0
    names, st, en = trace.ops()
    for name, s, e in zip(names, st, en):
        if match(op_name(name)) and s >= lo and e <= hi:
            n += 1
            tot += int(e) - int(s)
    return n, tot * 1e-9
