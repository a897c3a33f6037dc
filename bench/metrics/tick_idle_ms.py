"""Device: the chip's idle time between one decode step and the next, in
milliseconds, median over the consecutive ``jit_decode_step`` executions
of the traced window with no admission (``repro.runner.admit``) starting
between them (``program_spans.ticks``).  This is the host's turnaround per
tick: the logits' copy, sampling, the scheduler's own work and the next
launch.  Moves itl_p95_ms."""
import program_spans


def read(ctx):
    idle = []
    for a, b in program_spans.ticks(ctx):
        idle.append(sum(max(0.0, min(g1, b.start) - max(g0, a.end))
                        for g0, g1, _ in ctx.reduced.gaps))
    return program_spans.median_ms(idle)
