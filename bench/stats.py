"""Window accounting: the end-to-end metrics from a ``loadgen.Recorder``.

The window is [w0, w1) in seconds after the traffic's origin.

* tokens_per_s: tokens that reached the host inside the window, over its
  length.
* ttft: for every request due inside the window, the time from its due
  time to its first token.  A request with no first token by w1 counts at
  its wait so far (w1 - due); none is dropped.
* itl: every gap between consecutive tokens of one request whose later
  token reached the host inside the window.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile, linear between order statistics (numpy's
    default); raises on an empty sample."""
    if len(values) == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_samples(due: Dict[int, float], tokens: Dict[int, List[float]],
                 w0: float, w1: float) -> List[float]:
    out = []
    for rid, d in due.items():
        if not w0 <= d < w1:
            continue
        ts = tokens.get(rid, [])
        out.append((ts[0] if ts and ts[0] < w1 else w1) - d)
    return out


def itl_samples(tokens: Dict[int, List[float]], w0: float, w1: float) -> List[float]:
    out = []
    for ts in tokens.values():
        out.extend(b - a for a, b in zip(ts, ts[1:]) if w0 <= b < w1)
    return out


def tokens_in(tokens: Dict[int, List[float]], w0: float, w1: float) -> int:
    return sum(1 for ts in tokens.values() for t in ts if w0 <= t < w1)


def end_to_end(due, tokens, w0: float, w1: float) -> Dict[str, float]:
    """tokens_per_s, ttft_p95_ms, itl_p95_ms and the sample counts."""
    ttft = ttft_samples(due, tokens, w0, w1)
    itl = itl_samples(tokens, w0, w1)
    return {
        "tokens_per_s": tokens_in(tokens, w0, w1) / (w1 - w0),
        "ttft_p95_ms": 1e3 * percentile(ttft, 95),
        "itl_p95_ms": 1e3 * percentile(itl, 95),
        "n_ttft": len(ttft),
        "n_itl": len(itl),
    }
