"""Runner: median host time of ``repro.runner.sample`` in the traced
window, in milliseconds: the next token of every slot from the logits on
the host.  Moves itl_p95_ms."""
import program_spans


def read(ctx):
    spans = program_spans.named(ctx, "runner.sample")
    return program_spans.median_ms([s.end - s.start for s in spans])
