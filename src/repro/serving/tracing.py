"""Spans at the serving tier's boundaries, on the profiler's clock.

``span(name, **attrs)`` marks one piece of the host loop: a scheduler tick
(``sched.step``), an admission (``runner.admit``), the launch of a decode
step (``runner.launch``), the wait for its logits (``runner.fetch``) and
sampling (``runner.sample``).  It is a
``jax.profiler.TraceAnnotation("repro.<name>", **attrs)``, so a profile
captured of a running server holds it on the device trace's clock, its
attrs as event stats; ``set_metadata(**attrs)`` adds attrs known only
inside the span.  A model with experts puts its dispatch counts on
``runner.fetch``, read from the device with the step's logits: for the
decode step ``moe_held`` (routed assignments that landed on the experts
this chip holds, summed over the MoE layers), ``moe_rows`` (the rows the
held experts' projections computed) and ``moe_dropped`` (held assignments
past capacity), and ``admit_moe_*`` for the admissions since the last
fetch.  With no profiler running an annotation costs well under
a microsecond.  Nothing here reads a clock: no decision of the scheduler
or runner depends on a span, and tokens are the same with a profile
captured or not.
"""
from __future__ import annotations

import jax

PREFIX = "repro."


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """``with span("sched.step", tick=3) as sp: ... sp.set_metadata(rows=2)``"""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)
