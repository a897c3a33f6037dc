"""Runner: median host time of ``ModelRunner.decode``, which copies the
logits to the host and so includes the device step and the transfer, over
decode calls inside the measured window.  Moves itl_p95_ms."""
import numpy as np


def read(ctx):
    ms = [1e3 * (t1 - t0) for t0, t1, _ in ctx.spans.get("decode", [])
          if ctx.w0 <= t0 - ctx.rec.origin < ctx.w1]
    return float(np.median(ms)) if ms else None
