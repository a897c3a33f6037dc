"""Scheduler: the scheduler's own host time per tick, in milliseconds:
``repro.sched.step`` less the time that the spans inside it cover (the
runner's calls, and the benchmark's wrappers around them, whose block on
an admission's prefill is the benchmark's), median over the ticks of the
traced window.  What is left is expiry, the EDF search, block accounting,
streaming and retiring.  Moves itl_p95_ms."""
import program_spans


def read(ctx):
    spans = program_spans.of(ctx)
    self_ns = []
    for step in program_spans.named(ctx, "sched.step"):
        inside = [(s.start, s.end) for s in spans if s is not step and s.line == step.line
                  and step.start <= s.start and s.end <= step.end]
        self_ns.append(step.end - step.start - program_spans.covered_ns(inside))
    return program_spans.median_ms(self_ns)
