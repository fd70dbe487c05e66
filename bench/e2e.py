"""End-to-end arithmetic: what the client saw, reduced to the metrics.

Every online request due inside the window counts.  Time to first token is
taken from the request's *due* time (open loop), so a request that waited
to be sent, because the host was busy with a dispatch, pays that wait.  A
request that failed, or did not finish, counts as missing: it sorts above
every finished one (infinite latency).  Offline throughput counts only
tokens produced inside the window.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import numpy as np


@dataclass
class OnlineRecord:
    due: float                      # when the request was due to be sent
    want: int                       # max_tokens asked for
    prompt: tuple = ()
    in_window: bool = True
    t_send: Optional[float] = None  # when the generator got to send it
    t_first: Optional[float] = None  # first token frame at the client
    t_last: Optional[float] = None
    rid: Optional[str] = None
    tokens: List[int] = field(default_factory=list)
    status: str = 'pending'         # 'ok' | 'failed: ...' | 'pending'

    @property
    def ok(self) -> bool:
        return self.status == 'ok'

    @property
    def ttft(self) -> float:
        return self.t_first - self.due if self.ok else math.inf

    @property
    def tpot(self) -> Optional[float]:
        if not self.ok or len(self.tokens) < 2:
            return None
        return (self.t_last - self.t_first) / (len(self.tokens) - 1)


def percentile(values: Sequence[float], q: float) -> float:
    """numpy's linear percentile; infinities (missing requests) sort last."""
    if not len(values):
        return math.nan
    v = np.sort(np.asarray(values, float))
    if math.isinf(v[min(int(math.ceil(q / 100 * (len(v) - 1))),
                        len(v) - 1)]):
        return math.inf
    return float(np.percentile(v, q))


def online_metrics(records: Sequence[OnlineRecord]) -> dict:
    win = [r for r in records if r.in_window]
    ttft = [r.ttft for r in win]
    tpot = [t for t in (r.tpot for r in win) if t is not None]
    return {
        'n': len(win),
        'failed': sum(not r.ok for r in win),
        'ttft_p50_ms': 1e3 * percentile(ttft, 50),
        'ttft_p90_ms': 1e3 * percentile(ttft, 90),
        'tpot_p90_ms': 1e3 * percentile(tpot, 90) if tpot else math.nan,
        'n_tpot': len(tpot),
        'send_late_p90_ms': 1e3 * percentile(
            [r.t_send - r.due for r in win if r.t_send is not None], 90),
    }


def tokens_in_window(steps, klass: str, w0: float, w1: float) -> int:
    """Tokens that ``klass`` engines produced in steps ending in [w0, w1)."""
    return sum(s.tokens for s in steps
               if s.klass == klass and w0 <= s.t1 < w1)
