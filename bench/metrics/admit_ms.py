"""Runner: median host time of ``ModelRunner.admit_slot`` (a batch-1
bucketed prefill and the scatter of its cache into the slot pool), blocked
on the returned cache, over admissions inside the measured window.  The
block happens in traced runs only.  The ITL tail is the decode ticks that
carry an admission.  Moves itl_p95_ms."""
import numpy as np


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("admit", [])
          if ctx.w0 <= t0 - ctx.rec.origin < ctx.w1]
    return float(np.median(ms)) if ms else None
