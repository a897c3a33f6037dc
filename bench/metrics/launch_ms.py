"""Runner: how long the chip waits on the launch of a decode step, in
milliseconds: the start of the ``jit_decode_step`` execution on the chip
minus the start of the ``repro.runner.launch`` that dispatched it, median
over the ticks ``tick_idle_ms`` reads.  The launch span itself runs on
after the step has started; that part hides behind the device step and
is left out.  The launch of step b is the one that starts between the
previous step's start and b's.  Moves itl_p95_ms."""
import program_spans


def read(ctx):
    launches = [s.start for s in program_spans.named(ctx, "runner.launch")]
    waits = []
    for a, b in program_spans.ticks(ctx):
        starts = [t for t in launches if a.start < t < b.start]
        if len(starts) == 1:
            waits.append(b.start - starts[0])
    return program_spans.median_ms(waits)
