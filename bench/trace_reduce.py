"""Reduce a JAX profiler trace (``*.xplane.pb``) to device busy and idle
time, per-operation and per-program device time, and idle gaps labelled by
what the host was doing.

Layout of a TPU trace as JAX 0.9 writes it: one plane per chip named
``/device:TPU:<i>`` with an ``XLA Modules`` line (one event per program
execution, named ``jit_<fn>(<hash>)``) and an ``XLA Ops`` line (one event
per HLO operation, named by its HLO text ``%<op> = ...``; a Pallas kernel
is a custom call named after its kernel function, ``%crossbar_vmm_pallas.42``).
Control-flow operations (``while``, ``conditional``, ``call``) span their
bodies and are left out of per-operation time.  Host spans are the
``/host:CPU`` plane's events; the benchmark's own are named ``bench.<what>``.
All events share one clock, in nanoseconds.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

CONTAINERS = ("while", "conditional", "call")
HOST_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    name: str  # the HLO op's name, e.g. "crossbar_vmm_pallas.42"
    program: str  # the program it ran in, e.g. "jit_decode_step"
    start: float  # ns
    end: float


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]  # ns
    busy_ns: float  # union of operation intervals, averaged over the chips
    ops: List[Op]  # every non-container operation inside the window, all chips
    programs: List[Op]  # every program execution wholly inside the window, all chips
    gaps: List[Tuple[float, float, str]]  # idle intervals of chip 0, host label
    n_chips: int

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per ``program:op`` (op numbers kept), all chips."""
        out = collections.Counter()
        for o in self.ops:
            out[f"{o.program}:{o.name}"] += (o.end - o.start) / 1e9
        return dict(out)

    def program_seconds(self) -> Dict[str, float]:
        out = collections.Counter()
        for p in self.programs:
            out[p.program] += (p.end - p.start) / 1e9
        return dict(out)


def find_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, found {files}")
    return files[0]


def op_name(event_name: str) -> str:
    """``%copy.107 = f32[...] copy(...)`` -> ``copy.107``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def program_name(event_name: str) -> str:
    """``jit_decode_step(4900243105245156193)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w: Tuple[float, float]) -> Optional[Tuple[float, float]]:
    a, b = max(a, w[0]), min(b, w[1])
    return (a, b) if b > a else None


def host_spans(profile) -> List[Tuple[str, float, float]]:
    """The benchmark's own host spans: (name without prefix, start, end)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    out.append((ev.name[len(HOST_PREFIX):], ev.start_ns, ev.end_ns))
    return out


def _label(t: float, spans: List[Tuple[str, float, float]]) -> str:
    """The innermost host span covering ``t`` (the latest to start), or
    ``none``."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or a > best[1]):
            best = (name, a)
    return best[0] if best else "none"


def reduce(profile, window: Optional[Tuple[float, float]] = None) -> Reduced:
    """Reduce a loaded ``jax.profiler.ProfileData``.  ``window`` (ns) defaults
    to the extent of the host span ``bench.window``, else of all device
    events."""
    spans = host_spans(profile)
    if window is None:
        marks = [(a, b) for name, a, b in spans if name == "window"]
        window = marks[0] if marks else None
    chips = [p for p in profile.planes if p.name.startswith("/device:TPU:")]
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    per_chip_ops, programs = [], []
    for plane in chips:
        lines = {line.name: line for line in plane.lines}
        progs = [(program_name(e.name), e.start_ns, e.end_ns)
                 for e in (lines["XLA Modules"].events if "XLA Modules" in lines else [])]
        ops = [(op_name(e.name), e.start_ns, e.end_ns)
               for e in (lines["XLA Ops"].events if "XLA Ops" in lines else [])]
        per_chip_ops.append((progs, ops))
    if window is None:
        lo = min(a for progs, ops in per_chip_ops for _, a, _ in ops)
        hi = max(b for progs, ops in per_chip_ops for _, _, b in ops)
        window = (lo, hi)
    busy, all_ops, gaps = [], [], []
    for ci, (progs, ops) in enumerate(per_chip_ops):
        progs.sort(key=lambda p: p[1])
        starts = [p[1] for p in progs]

        def owner(t):
            i = bisect.bisect_right(starts, t) - 1
            return progs[i][0] if i >= 0 and progs[i][2] >= t else "?"

        clipped = []
        for name, a, b in ops:
            c = _clip(a, b, window)
            if c is None:
                continue
            clipped.append(c)
            if name.split(".", 1)[0] not in CONTAINERS:
                all_ops.append(Op(name, owner(a), *c))
        union = _union(clipped)
        busy.append(sum(b - a for a, b in union))
        programs += [Op(name, name, a, b) for name, a, b in progs
                     if window[0] <= a and b <= window[1]]
        if ci == 0:
            edges = [window[0]] + [t for ab in union for t in ab] + [window[1]]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((a, b, _label((a + b) / 2, spans)))
    return Reduced(window, sum(busy) / len(busy), all_ops, programs, gaps, len(chips))


def breakdown(r: Reduced, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time, and the longest idle
    gaps summed by what the host was doing, each as [name, seconds]."""
    ops = sorted(r.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    idle = collections.Counter()
    for a, b, label in r.gaps:
        idle[label] += (b - a) / 1e9
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in idle.most_common(top)],
    }
