"""MoE: the share of the rows the held experts' projections compute that
carry a routed assignment, in percent: 100 x the assignments that landed
on the experts this chip holds over the rows of their buffers, summed over
the MoE layers and over the decode ticks and admissions whose counts came
to the host in the traced window (``repro.runner.fetch`` attrs ``moe_held``,
``moe_rows`` and ``admit_moe_held``, ``admit_moe_rows``).  A dropless
buffer holds a row per token for each held expert, so with routing spread
evenly this reads top-k over the router's width.  Reports nothing where
the program counts no dispatch.  Moves itl_p95_ms."""
import program_spans


def read(ctx):
    fetches = [s.attrs for s in program_spans.named(ctx, "runner.fetch")]
    held = sum(a.get("moe_held", 0) + a.get("admit_moe_held", 0) for a in fetches)
    rows = sum(a.get("moe_rows", 0) + a.get("admit_moe_rows", 0) for a in fetches)
    return 100.0 * held / rows if rows else None
