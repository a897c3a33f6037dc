"""The program's own spans in a traced run, and the decode ticks that the
device-clock readers share.

The serving program marks its scheduler ticks and runner calls with
``repro.<name>`` annotations (``repro.serving.tracing``): ``sched.step``
(attrs ``tick``, ``rows``, ``admitted``), ``runner.admit`` (``rid``,
``slot``, ``prompt``, ``bucket``), ``runner.launch``, ``runner.fetch`` and
``runner.sample``.  A ``--trace 1`` run's profile holds them on the host
plane, on the device trace's clock, beside the benchmark's ``bench.*``
spans.  They are read here from the profile ``bench/run.py`` leaves in
``.bench_trace/<cell>``, once per run, and kept on the readers' context.
A program without those spans yields none, and every reader then reports
nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Tuple

import jax

import harness
import trace_reduce

PROGRAM = "repro."
STEP = "jit_decode_step"


@dataclasses.dataclass
class Span:
    name: str  # as annotated: "repro.runner.fetch", "bench.decode"
    start: float  # ns, the profile's clock
    end: float
    attrs: Dict
    line: str  # host plane and thread: spans nest only within one


def spans(profile) -> List[Span]:
    """Every ``repro.*`` and ``bench.*`` span on the profile's host planes,
    in start order; none where the profile holds no ``repro.*`` span."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((PROGRAM, trace_reduce.HOST_PREFIX)):
                    out.append(Span(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats),
                                    f"{plane.name}/{line.name}"))
    if not any(s.name.startswith(PROGRAM) for s in out):
        return []
    return sorted(out, key=lambda s: s.start)


def of(ctx) -> List[Span]:
    """The spans of ``ctx``'s traced run that start inside its traced
    window, read from the run's profile on first use."""
    if getattr(ctx, "program_spans", None) is None:
        directory = harness.ROOT / ".bench_trace" / ctx.cell.name
        profile = jax.profiler.ProfileData.from_file(trace_reduce.find_xplane(str(directory)))
        ctx.program_spans = spans(profile)
    lo, hi = ctx.reduced.window
    return [s for s in ctx.program_spans if lo <= s.start < hi]


def named(ctx, name: str) -> List[Span]:
    return [s for s in of(ctx) if s.name == PROGRAM + name]


def covered_ns(intervals: List[Tuple[float, float]]) -> float:
    """The length of the union of ``intervals``."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def median_ms(values_ns: List[float]) -> Optional[float]:
    return statistics.median(values_ns) / 1e6 if values_ns else None


def ticks(ctx) -> List[Tuple[trace_reduce.Op, trace_reduce.Op]]:
    """Consecutive ``jit_decode_step`` executions (a, b) of the traced
    window with no ``runner.admit`` starting between their starts: the
    chip's turnaround from one decode step to the next with no prefill in
    it.  Empty where the program has no tick spans."""
    if not named(ctx, "sched.step"):
        return []
    steps = sorted((p for p in ctx.reduced.programs if p.program == STEP),
                   key=lambda p: p.start)
    admits = [s.start for s in named(ctx, "runner.admit")]
    out = []
    for a, b in zip(steps, steps[1:]):
        i = bisect.bisect_right(admits, a.start)
        if i == len(admits) or admits[i] >= b.start:
            out.append((a, b))
    return out
