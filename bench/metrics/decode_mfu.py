"""Model step: the decode steps' share of the chip's bf16 peak, over the
traced window.  Model operations per step are counted from shapes by the
cell's reference (``decode_model_flops``: for a Llama, 2 per programmed
weight per active row, plus attention over each active row's context);
the time is the device time of the ``jit_decode_step`` programs that ran
whole inside the traced window.  Moves itl_p95_ms."""
import flops

PROGRAM = "jit_decode_step"


def read(ctx):
    r = ctx.reduced
    steps = [p for p in r.programs if p.program == PROGRAM]
    a, b = ctx.traced
    calls = [m["contexts"] for t0, t1, m in ctx.spans.get("decode", []) if a <= t0 and t1 <= b]
    if not steps or not calls:
        return None
    per_step = sum(ctx.ref.decode_model_flops(ctx.dims, c) for c in calls) / len(calls)
    device_s = sum(p.end - p.start for p in steps) / 1e9 / len(steps)
    return 100.0 * per_step / (device_s * flops.peaks(ctx.device_kind)["bf16_flops_per_s"])
