"""Open-loop load generator: a traffic mix (a data file of lengths and
arrivals) turned into a fixed list of requests, and the loop that offers
them to the scheduler on the host clock.

Every seed gets the same sizes and arrivals: lengths and inter-arrival
gaps are the quantiles of their distributions, taken in blocks of
``block`` requests and shuffled within each block in one fixed order.  A
window holds a few dozen requests whose lengths span two orders of
magnitude, so an order drawn from the seed would change the work in the
window (6% in tokens per second between seeds against 1% between two runs
of one seed, on the chip).  The seed draws the token ids.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from statistics import NormalDist
from typing import Dict, List

import numpy as np

ORDER_SEED = 0


@dataclasses.dataclass(frozen=True)
class Planned:
    due: float  # seconds after the traffic's origin
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a lognormal (median, sigma), rounded
    and clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def plan(traffic: Dict, rate: float, seconds: float, vocab: int, seed: int) -> List[Planned]:
    """Requests due over ``seconds`` of arrivals at ``rate`` req/s, plus a
    quarter more so that a late run does not run dry."""
    block = int(traffic["block"])
    n = block * math.ceil(rate * seconds * 1.25 / block + 1)
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    prompts = _lognormal_quantiles(traffic["prompt"], block)
    outputs = _lognormal_quantiles(traffic["output"], block)
    # exponential gaps at the same stratified quantiles: a Poisson process
    gaps = -np.log1p(-(np.arange(block) + 0.5) / block) / rate
    out, t = [], 0.0
    for _ in range(n // block):
        for p, o, g in zip(order.permutation(prompts), order.permutation(outputs),
                           order.permutation(gaps)):
            t += float(g)
            ids = rng.integers(0, vocab, size=int(p)).astype(np.int32)
            out.append(Planned(t, ids, int(o)))
    return out


class Recorder:
    """Host-clock record of one run of traffic: when each request was due,
    when each of its tokens reached the host, and how late the generator
    submitted.  Times are seconds after the traffic's origin."""

    def __init__(self, origin: float):
        self.origin = origin
        self.due: Dict[int, float] = {}
        self.tokens: Dict[int, List[float]] = {}
        self.lag: Dict[int, float] = {}  # how late each was submitted

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def on_token(self, req, tok: int) -> None:
        self.tokens.setdefault(req.rid, []).append(self.now())


def drive(sched, planned: List[Planned], rec: Recorder, until: float,
          start: int = 0, spans=None) -> int:
    """Offer ``planned[start:]`` as each falls due, stepping the scheduler
    between submissions, until ``until`` seconds after the origin.  Returns
    the index of the first request not yet submitted.  ``spans`` (a
    ``harness.Spans``) records the generator's submissions, its waits for
    the next arrival, and each scheduler step."""
    span = spans.span if spans is not None else (lambda name, **kw: contextlib.nullcontext())
    i = start
    while True:
        now = rec.now()
        if now >= until:
            return i
        with span("generator"):
            while i < len(planned) and planned[i].due <= now:
                p = planned[i]
                rid = sched.submit(p.prompt, max_new_tokens=p.max_new)
                rec.due[rid] = p.due
                rec.lag[rid] = now - p.due
                i += 1
        if sched.load == 0:
            nxt = planned[i].due if i < len(planned) else until
            with span("wait"):
                time.sleep(max(0.0, min(nxt, until) - rec.now()))
            continue
        with span("step"):
            sched.step()
