"""Runner: how long the host waits after a decode step ends on the chip
until its logits are on the host, in milliseconds: the end of
``repro.runner.fetch`` minus the end of the ``jit_decode_step`` execution
it waited on, median over the ticks ``tick_idle_ms`` reads.  The fetch
that waited on step a is the one that ends between a's start and the next
step's: it ends before the next launch.  Moves itl_p95_ms."""
import program_spans


def read(ctx):
    fetches = [f.end for f in program_spans.named(ctx, "runner.fetch")]
    waits = []
    for a, b in program_spans.ticks(ctx):
        ends = [t for t in fetches if a.start < t < b.start]
        if len(ends) == 1:
            waits.append(ends[0] - a.end)
    return program_spans.median_ms(waits)
