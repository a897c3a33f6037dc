"""Runner: the prefill's useful share of the work it does, in percent:
100 x the prompt tokens over the padded bucket lengths that prefill
computes, summed over the admissions (``repro.runner.admit``, attrs
``prompt`` and ``bucket``) of the traced window.  Moves itl_p95_ms, whose
tail is the ticks that carry an admission."""
import program_spans


def read(ctx):
    admits = program_spans.named(ctx, "runner.admit")
    if not admits:
        return None
    return 100.0 * sum(s.attrs["prompt"] for s in admits) / sum(s.attrs["bucket"] for s in admits)
