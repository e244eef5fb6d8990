"""Client-side arithmetic over one run's timeline (host clock).

Percentiles interpolate linearly between order statistics (numpy's
default).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """One request as the client saw it; times on the host clock."""

    prompt_len: int
    token_s: List[float] = dataclasses.field(default_factory=list)


def percentile(values, q: float) -> Optional[float]:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def gaps(records: List[Record], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive tokens of one request that ends
    inside ``(t0, t1]``."""
    out = []
    for r in records:
        ts = r.token_s
        out += [b - a for a, b in zip(ts, ts[1:]) if t0 < b <= t1]
    return out


def tokens_in(records: List[Record], t0: float, t1: float) -> int:
    return sum(t0 < t <= t1 for r in records for t in r.token_s)


def end_to_end(records: List[Record], t0: float, t1: float) -> Dict[str, float]:
    """The end-to-end readings of one window ``(t0, t1]``."""
    return {"tokens_per_s": tokens_in(records, t0, t1) / (t1 - t0),
            "tbt_p95_s": percentile(gaps(records, t0, t1), 95)}
