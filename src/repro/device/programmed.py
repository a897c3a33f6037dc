"""Program-once crossbar compilation: frozen programmed-weight artifacts.

Newton's core premise is that weights are programmed into crossbars *once*
and then serve in-situ traffic indefinitely — programming (fault draw,
write-verify pulses, IR-drop solve, quantization-scale reductions) is a
deployment-time cost, not a per-call one.  The pre-existing hot path
re-ran that whole pipeline inside every ``crossbar_matmul(device=...)``
call; this module splits the stack into an explicit **programming time**
vs **inference time**:

* ``program_layer(w, spec, device, adc_cfg) -> ProgrammedLinear`` — compile
  one float weight matrix into a frozen pytree artifact: quantized cell
  codes, the device-perturbed effective cells (``g_eff``), the static
  ``QuantParams``, the ``layer_scaled_spec``, the digital correction column
  sums, and the write-verify ``ProgramReport`` metadata.
* ``programmed_matmul(x, art)`` / ``programmed_linear(x, art)`` — the
  steady-state forward: input quantization -> Pallas kernel -> dequantize.
  No ``jnp.max(w)`` reductions, no ``effective_cell_codes``, no per-call
  fault redraw.  Noisy runs become self-consistent: one fixed programmed
  chip serves the whole inference run instead of a fresh noise draw per
  layer call.
* ``program_model(params, ...) -> ProgrammedModel`` — walk a parameter
  pytree and compile every matmul-shaped leaf.  Artifacts are **keyed by
  the joined parameter path** ("stage0/b0/mixer/wq"), not by leaf object
  identity: a pytree copy (``jax.device_put``, donation, optimizer step,
  checkpoint restore), a fresh jit trace, or a transpose view all resolve
  to the same artifact, because the *name* is stable where the array
  object is not.  ``models.layers.crossbar_linear(x, w, name=...)`` joins
  the call-site name with the active ``name_scope`` stack (pushed by
  ``models.model`` as it descends stages/blocks/submodules) and looks the
  key up in the dynamic ``bind_artifacts`` stack first (scan-sliced
  per-layer bindings) and the model's ``by_name`` table second.

Everything static (spec, scales, ADC config, report) rides in the pytree
*aux* so a ``ProgrammedLinear`` can be passed through ``jax.jit`` or closed
over as a constant; the arrays (``w_codes``, ``g_eff``, ``w_colsum``) are
ordinary leaves.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.adc import ADCConfig, SAFE_ADAPTIVE
from repro.core.crossbar import (
    CrossbarSpec,
    DEFAULT_SPEC,
    QuantParams,
    layer_scaled_spec,
    quantize_input,
    quantize_weight,
)
from repro.device import models as dm
from repro.device.program import ProgramReport


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ProgrammedLinear:
    """One weight matrix compiled onto (possibly noisy) crossbars.

    Array leaves (all become scan/vmap-sliceable pytree children):
      * ``w_codes``: (K, N) int32 signed quantized weight codes — the ideal
        cells, consumed directly by the bit-slicing Pallas kernel.
      * ``g_eff``: (S, K, N) float32 device-perturbed effective cell codes,
        or None for ideal devices (then ``w_codes`` is the ground truth).
      * ``w_colsum``: (N,) float32 column sums of the *float* weights — the
        digital offset-correction term ``crossbar_linear`` needs (computed
        at write time on real hardware, alongside the biased column sums
        inside the kernels' requantize stage).
      * ``w_scale``: 0-d float32 — the frozen weight quantization scale (the
        ``max |w|`` reduction, paid once at programming time).
      * ``x_scale``: 0-d float32 or None — frozen input scale; None keeps
        input quantization dynamic (per-call ``max(x)``, exactly matching
        the unprogrammed path, or per row where ``spec.row_ranges``).
      * ``g_spare``: (S, K, B) float32 programmed spare-column cells, or
        None when the device provisions no repair (``device.repair``).
        ``g_eff`` already holds the *repaired* layout (spares scattered into
        victim positions at programming time — zero steady-state overhead);
        the spare block plus ``out_gather`` are the explicit hardware
        record: the redundant columns as programmed and the column-mux
        routing table.
      * ``out_gather``: (S, R, N) int32 or None — per-physical-crossbar
        routing tables (R = row groups): the physical column serving each
        logical output within that (slice, row group) array (j, or N + b
        for repaired units).
      * ``comp_scale``: (N,) float32 or None — drift-compensating *digital*
        per-column output scales (``device.health.fit_compensation``).
        They live outside the chip — updating them costs no reprogramming —
        and are applied after the dequantize, before the offset-correction
        colsum.  None (fresh chips) is a bit-exact no-op.

    **Service time**: a programmed chip decays in service (power-law
    retention drift).  ``age(dt_s)`` / ``at_time(t_s)`` return a
    drift-evolved view of the same chip — ``g_eff``/``g_spare`` decayed
    through the device's level map, ``t_service_s`` advanced — without
    reprogramming; the digital record (``w_codes``, ``w_colsum``) is
    immortal and stays the frozen reference the health monitor probes
    against.  Aging a drift-free chip only advances the clock
    (bit-identical arrays).

    A *stacked* artifact (from a ``(L, K, N)`` scan-stacked parameter leaf)
    carries a leading layer axis on every array; ``jax.lax.scan`` /
    ``tree.map(lambda a: a[i])`` slice it back to a servable per-layer
    artifact (``models.model._run_stage`` does exactly this).

    Static aux (hashable; part of the jit cache key): ``spec`` — the
    layer-scaled ``CrossbarSpec`` (``drop_lsb`` already chosen for this K);
    ``adc_cfg`` / ``fast`` — which kernel path serves this artifact;
    ``report`` — optional write-verify ``ProgramReport``; ``repair`` —
    optional ``repair.RepairReport`` (tuples of them for stacked artifacts);
    ``device`` — the ``DeviceConfig`` the chip was programmed with (the
    lifecycle layer needs its drift law and level map to age the chip);
    ``t_service_s`` — seconds of service since programming; ``plan`` — the
    optional ``core.planner.LayerPlan`` this chip was compiled under: which
    datapath serves it (direct / Karatsuba levels / Strassen — executed by
    ``programmed_matmul`` on ideal chips, bit-identical by exact limb
    arithmetic), which ADC schedule, and the spare/replication budgets the
    programming pass materialized.
    """

    w_codes: jnp.ndarray
    g_eff: Optional[jnp.ndarray]
    w_colsum: jnp.ndarray
    w_scale: jnp.ndarray
    x_scale: Optional[jnp.ndarray]
    spec: CrossbarSpec
    adc_cfg: Optional[ADCConfig] = None
    fast: bool = True
    report: Optional[Any] = None
    g_spare: Optional[jnp.ndarray] = None
    out_gather: Optional[jnp.ndarray] = None
    repair: Optional[Any] = None
    comp_scale: Optional[jnp.ndarray] = None
    device: Optional[dm.DeviceConfig] = None
    t_service_s: float = 0.0
    plan: Optional[Any] = None  # core.planner.LayerPlan (static, hashable)

    @property
    def noisy(self) -> bool:
        return self.g_eff is not None

    def age(self, dt_s: float) -> "ProgrammedLinear":
        """Advance the chip ``dt_s`` seconds of service (drift-evolved view)."""
        return age_artifact(self, dt_s)

    def at_time(self, t_s: float) -> "ProgrammedLinear":
        """The chip at absolute service time ``t_s >= t_service_s``."""
        return artifact_at_time(self, t_s)

    @property
    def stacked(self) -> bool:
        """Carries leading stacking axes beyond the servable (K, N) matrix:
        (L, K, N) scan-stacked layers, (E, K, N) expert stacks, or the
        (L, E, K, N) combination.  ``layer(i)`` peels one leading axis."""
        return self.w_codes.ndim >= 3

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.w_codes.shape)

    @property
    def qp(self) -> QuantParams:
        """Static view of the frozen quantization scales (introspection)."""
        if self.stacked:
            raise ValueError(
                "stacked artifact holds per-layer scales: use art.layer(i).qp"
            )
        return QuantParams(
            x_scale=(float(self.x_scale) if self.x_scale is not None else 0.0),
            w_scale=float(self.w_scale),
        )

    def layer(self, i: int) -> "ProgrammedLinear":
        """Slice one layer out of a stacked artifact."""
        assert self.stacked, "layer() only applies to stacked artifacts"
        return jax.tree.map(lambda a: a[i], self)

    def tree_flatten(self):
        children = (
            self.w_codes, self.g_eff, self.w_colsum, self.w_scale, self.x_scale,
            self.g_spare, self.out_gather, self.comp_scale,
        )
        aux = (
            self.spec, self.adc_cfg, self.fast, self.report, self.repair,
            self.device, self.t_service_s, self.plan,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (w_codes, g_eff, w_colsum, w_scale, x_scale, g_spare, out_gather,
         comp_scale) = children
        spec, adc_cfg, fast, report, repair, device, t_service_s, plan = aux
        return cls(
            w_codes, g_eff, w_colsum, w_scale, x_scale, spec, adc_cfg, fast,
            report, g_spare=g_spare, out_gather=out_gather, repair=repair,
            comp_scale=comp_scale, device=device, t_service_s=t_service_s,
            plan=plan,
        )


# Every array leaf a ProgrammedLinear carries — the single source of truth
# for serialization (checkpoint.save_programmed) and equality checks.
ARTIFACT_ARRAY_FIELDS = (
    "w_codes", "g_eff", "w_colsum", "w_scale", "x_scale", "g_spare", "out_gather",
    "comp_scale",
)


def artifacts_equal(a: "ProgrammedLinear", b: "ProgrammedLinear") -> bool:
    """Bit-exact artifact equality: every array field (None-ness included)
    plus the static datapath aux (spec / adc_cfg / fast) and the lifecycle
    state (device / t_service_s — two chips at different service times are
    different chips).  Reports are observability metadata and deliberately
    not part of chip equality."""
    for f in ARTIFACT_ARRAY_FIELDS:
        va, vb = getattr(a, f), getattr(b, f)
        if (va is None) != (vb is None):
            return False
        if va is not None and not bool(jnp.array_equal(va, vb)):
            return False
    return (
        a.spec == b.spec
        and a.adc_cfg == b.adc_cfg
        and a.fast == b.fast
        and a.device == b.device
        and a.t_service_s == b.t_service_s
        and a.plan == b.plan
    )


def program_layer(
    w: jnp.ndarray,
    spec: CrossbarSpec = DEFAULT_SPEC,
    device: Optional[dm.DeviceConfig] = None,
    adc_cfg: Optional[ADCConfig] = SAFE_ADAPTIVE,
    *,
    x_scale: Optional[float] = None,
    w_scale: Optional[float] = None,
    fast: bool = True,
    with_report: bool = False,
    chips: Optional[Tuple[int, ...]] = None,
    plan: Optional[Any] = None,
) -> ProgrammedLinear:
    """Compile one (K, N) — or stacked (L, K, N) / (L, E, K, N) — weight.

    This is the *programming-time* entry point — it runs every expensive,
    weight-only stage exactly once: the ``max |w|`` scale reduction, weight
    quantization, the device fault draw + write-verify pulse loop + read
    path (``effective_cell_codes``), and the correction column sums.  It is
    deterministic in (w, spec, device): programming twice yields the same
    chip, bit for bit, as the old program-every-call path drew per call.

    ``x_scale=None`` keeps input quantization dynamic (per-call ``max(x)``),
    matching the unprogrammed path exactly; pass a calibrated scale for
    fully static serving.  ``with_report=True`` routes programming through
    ``program.write_verify`` for convergence metadata (bit-identical cells).

    Stacked leaves recurse over every leading axis: a scan-stacked MoE
    expert bank ``(L, E, d_model, d_ff)`` compiles to an artifact whose
    arrays carry ``(L, E, ...)`` — the layer scan slices ``L``, the
    per-expert scan inside ``models.moe`` slices ``E``.

    ``chips`` models chip-to-chip fleet spread: one ``DeviceConfig.chip``
    identity per slice of the *innermost* stacking axis (the expert axis
    for a 4-D bank, the layer axis for 3-D), so the slabs an EP mesh places
    on different ranks draw decorrelated device perturbations — the same
    expert weights on chip 3 and chip 5 are different physical dies.  The
    stacked artifact's ``device`` aux keeps the base config (chip as
    passed): aging depends only on the drift law, which the spread does not
    touch.  ``chips=None`` (default) is bit-compatible with every
    pre-lifecycle artifact.

    ``plan`` (a ``core.planner.LayerPlan``) compiles the chip under the
    plan compiler's per-layer choices: the ADC config is materialized from
    the plan's mode against the layer-scaled spec, a positive planned
    spare-column budget overrides the device's (only when the device has
    stuck faults to repair — a plan cannot conjure a fault model), and the
    plan rides the artifact's static aux so ``programmed_matmul`` executes
    the chosen datapath.  ``plan=None`` is the homogeneous compile,
    bit-compatible with every pre-planner artifact.
    """
    w = jnp.asarray(w, jnp.float32)
    if w.ndim >= 3:  # stacked (L/E leading axes): compile per slice, stack
        if chips is not None and w.ndim == 3:
            if device is None:
                raise ValueError("chips= requires a DeviceConfig")
            if len(chips) != w.shape[0]:
                raise ValueError(
                    f"chips has {len(chips)} entries for stacking axis "
                    f"of {w.shape[0]}"
                )
            devices = [
                dataclasses.replace(device, chip=int(c)) for c in chips
            ]
        else:  # 4-D: forward chips to the inner (expert) axis
            devices = [device] * w.shape[0]
        parts = [
            program_layer(
                w[i], spec, devices[i], adc_cfg, x_scale=x_scale,
                w_scale=w_scale, fast=fast, with_report=with_report,
                chips=(chips if w.ndim > 3 else None), plan=plan,
            )
            for i in range(w.shape[0])
        ]
        reports = tuple(p.report for p in parts)
        repairs = tuple(p.repair for p in parts)
        # per-layer reports differ, which would make the tree structures
        # unequal — strip them before stacking, reattach as tuples; the
        # per-slice device aux (chip spread) is likewise normalized to the
        # base config so every part flattens to the same treedef
        parts = [
            dataclasses.replace(p, report=None, repair=None, device=device)
            for p in parts
        ]
        out = jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
        return dataclasses.replace(
            out,
            report=(reports if any(r is not None for r in reports) else None),
            repair=(repairs if any(r is not None for r in repairs) else None),
        )
    spec = layer_scaled_spec(spec, w.shape[0])
    if plan is not None:
        from repro.core.planner import adc_config_for

        # materialize the plan's choices: ADC schedule against *this*
        # layer's scaled spec, spare budget onto the fault model (a plan
        # with spares but no faulty device to repair is a no-op, not an
        # error — the plan may have been compiled for a noisier deployment)
        adc_cfg = adc_config_for(plan.adc_mode, spec)
        if (
            plan.spare_cols > 0
            and device is not None
            and not device.is_ideal
            and (device.p_stuck_on > 0 or device.p_stuck_off > 0)
        ):
            device = dataclasses.replace(device, spare_cols=plan.spare_cols)
    if w_scale is None:
        # kept as a 0-d array so the steady-state dequantize is op-for-op
        # identical to the per-call path's traced scale
        w_scale_a = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9) / (
            (1 << (spec.weight_bits - 1)) - 1
        )
    else:
        w_scale_a = jnp.asarray(w_scale, jnp.float32)
    wq = quantize_weight(w, spec, w_scale_a)
    w_colsum = jnp.sum(w, axis=0)
    g_eff = None
    g_spare = None
    out_gather = None
    report = None
    repair_rep = None
    if device is not None and not device.is_ideal:
        wb = wq + spec.weight_bias
        # fault-aware spare-column repair (device.repair): remap the worst
        # fault-afflicted columns into programmed spares and bake the
        # repaired layout into g_eff — steady-state calls pay nothing.
        # repaired_effective_cells is the single derivation site for the
        # programming intermediates; with_report only adds observability
        # (bit-identical cells, pinned by test_programming_is_deterministic)
        from repro.device import repair as repair_mod

        if with_report:
            g_eff, rplan, report = repair_mod.repaired_effective_cells(
                wb, spec, device, with_report=True
            )
        else:
            g_eff, rplan = _programmed_cells(wb, spec, device)
        if rplan is not None:
            g_spare = rplan.g_spare
            out_gather = rplan.out_gather
            repair_rep = repair_mod.repair_report(rplan)
    return ProgrammedLinear(
        w_codes=wq, g_eff=g_eff, w_colsum=w_colsum,
        w_scale=w_scale_a,
        x_scale=(jnp.asarray(x_scale, jnp.float32) if x_scale is not None else None),
        g_spare=g_spare, out_gather=out_gather,
        spec=spec, adc_cfg=adc_cfg, fast=fast, report=report, repair=repair_rep,
        device=device, t_service_s=0.0, plan=plan,
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _programmed_cells(wb: jnp.ndarray, spec: CrossbarSpec, device: dm.DeviceConfig):
    """(repaired g_eff, repair plan) of one biased (K, N) code slab, as one
    compiled program.

    Run op by op, the programming pipeline dispatches hundreds of small
    operations, and an accelerator compiles each of them for every distinct
    slab shape; jitted, each (shape, spec, device) compiles once.
    """
    from repro.device import repair as repair_mod

    g_eff, rplan, _ = repair_mod.repaired_effective_cells(wb, spec, device)
    return g_eff, rplan


# ---------------------------------------------------------------------------
# Service-time aging (the chip lifecycle's clock)
# ---------------------------------------------------------------------------


def artifact_at_time(art: ProgrammedLinear, t_s: float) -> ProgrammedLinear:
    """The chip as it reads at absolute service time ``t_s``.

    Drift is monotone conductance loss — a programmed chip can only move
    forward in time (``t_s >= art.t_service_s``; rejuvenation means
    reprogramming, see ``ServingEngine.refresh``).  The decay between the
    two service times is a single scalar factor from the device's power law
    (``models.drift_time_factor``), pushed through the level map onto the
    stored effective cells (``models.age_effective_codes``) — works
    unchanged on stacked ``(L, …, S, K, N)`` arrays because the transform
    is elementwise.  The digital record (``w_codes``, ``w_colsum``,
    scales) never ages: it is the frozen reference the health monitor
    compares against.

    A drift-free chip (no device, ideal device, ``drift_nu == 0``) only
    advances the clock — the arrays are the same objects, bit-identical by
    construction.  The factor-1.0 short-circuit also matters for exactness:
    the code -> conductance -> code round trip re-snaps to the write grid
    and is not a float identity.
    """
    t_s = float(t_s)
    if t_s < art.t_service_s:
        raise ValueError(
            f"cannot rejuvenate a chip: at_time({t_s}) < current service "
            f"time {art.t_service_s} (reprogram instead)"
        )
    if art.g_eff is None or art.device is None:
        return dataclasses.replace(art, t_service_s=t_s)
    factor = dm.drift_time_factor(art.device, art.t_service_s, t_s)
    if factor == 1.0:
        return dataclasses.replace(art, t_service_s=t_s)
    g_eff = dm.age_effective_codes(art.g_eff, art.spec, art.device, factor)
    g_spare = (
        dm.age_effective_codes(art.g_spare, art.spec, art.device, factor)
        if art.g_spare is not None
        else None
    )
    return dataclasses.replace(
        art, g_eff=g_eff, g_spare=g_spare, t_service_s=t_s
    )


def age_artifact(art: ProgrammedLinear, dt_s: float) -> ProgrammedLinear:
    """Advance a chip ``dt_s >= 0`` seconds of service (see ``artifact_at_time``)."""
    if dt_s < 0:
        raise ValueError(f"dt_s must be non-negative, got {dt_s}")
    return artifact_at_time(art, art.t_service_s + float(dt_s))


def _over_range(reduce, x: jnp.ndarray, spec: CrossbarSpec) -> jnp.ndarray:
    """``reduce`` (``jnp.min``, ``jnp.max``) over the input's range: the
    whole call, or each row (kept as a trailing axis of 1) where
    ``spec.row_ranges``."""
    return reduce(x, axis=-1, keepdims=True) if spec.row_ranges else reduce(x)


def programmed_matmul(
    x: jnp.ndarray,
    art: ProgrammedLinear,
    interpret: Optional[bool] = None,
    skip_zero_planes: bool = True,
) -> jnp.ndarray:
    """Steady-state float crossbar matmul against a programmed artifact.

    The entire inference-time path: input quantization -> Pallas kernel ->
    dequantize — no weight reductions, no fault redraw.  Bit-identical to
    ``kernels.ops.crossbar_matmul(x, w, device=...)`` with the same
    quantization scales, but the programming pipeline has been amortized
    away, and repeated calls reuse the *same* programmed chip
    (self-consistent noise) instead of redrawing it.  ``x`` must be
    non-negative (see ``programmed_linear`` for the offset-encoded form).

    Deliberately *not* wrapped in an extra jit: the elementwise stages
    mirror ``crossbar_matmul`` op-for-op (XLA's scalar-chain reassociation
    inside a fused jit can perturb the dequantize product by 1 ULP,
    breaking the bit-identity guarantee vs the program-every-call path);
    the heavy kernel call is jitted already, and under an outer jit
    everything fuses anyway.
    """
    from repro.kernels.crossbar_vmm import crossbar_vmm_pallas
    from repro.kernels.noisy_vmm import noisy_vmm_pallas

    if art.stacked:
        raise ValueError(
            "stacked artifact: slice one layer first (art.layer(i), or let "
            "models.model._run_stage scan over it)"
        )
    if interpret is None:
        from repro.kernels.ops import _auto_interpret

        interpret = _auto_interpret()
    spec = art.spec
    if art.x_scale is not None:
        x_scale = art.x_scale
    else:
        # barrier: one canonical x_scale value feeds both the quantize and
        # the dequantize — without it XLA duplicates this cheap computation
        # into both consumer fusions, where it may lower differently (e.g.
        # divide vs reciprocal-multiply) and perturb the dequantize by an
        # output ULP; bit-identity across eager/jit/shard_map is a contract
        # here (tests/test_sharded_artifacts.py pins it on an 8-rank mesh)
        x_scale = jax.lax.optimization_barrier(
            jnp.maximum(_over_range(jnp.max, x, spec), 1e-9) / ((1 << spec.input_bits) - 1)
        )
    xq = quantize_input(x, spec, x_scale)
    datapath = art.plan.datapath if art.plan is not None else "direct"
    if art.g_eff is not None:
        # noisy chips always serve through the device kernel: the
        # effective-cell read models physical arrays, which the
        # divide-and-conquer datapaths re-tile rather than re-read — the
        # plan still governs the ADC schedule (adc_cfg below) and the spare
        # budget (baked into g_eff at programming time)
        yq = noisy_vmm_pallas(
            xq, art.g_eff, spec, adc_cfg=art.adc_cfg, interpret=interpret,
            skip_zero_planes=skip_zero_planes,
        )
    elif datapath != "direct":
        # planned heterogeneous datapath: exact limb arithmetic, so the
        # output codes are bit-identical to the direct kernel's (the
        # kernel_planned bench and tests/test_planner.py gate this)
        if datapath == "strassen":
            from repro.core.strassen import strassen_matmul

            lead = xq.shape[:-1]
            yq = strassen_matmul(
                xq.reshape(-1, xq.shape[-1]), art.w_codes, spec, levels=1
            ).reshape(lead + (art.w_codes.shape[-1],))
        else:
            from repro.core.karatsuba import karatsuba_vmm

            yq = karatsuba_vmm(
                xq, art.w_codes, spec, levels=art.plan.karatsuba_levels
            )
    elif art.fast:
        yq = crossbar_vmm_pallas(
            xq, art.w_codes, spec, adc_cfg=None, fast=True, interpret=interpret,
            skip_zero_planes=skip_zero_planes,
        )
    else:
        yq = crossbar_vmm_pallas(
            xq, art.w_codes, spec, adc_cfg=art.adc_cfg, interpret=interpret,
            skip_zero_planes=skip_zero_planes,
        )
    # dequantize with a pinned association order: the barrier keeps XLA's
    # algebraic simplifier from reassociating the scalar chain (folding
    # w_scale into the 2^drop constant under jit, which rounds differently
    # than the eager left-to-right product) — eager, jit and shard_map
    # executions of one artifact must dequantize bit-identically
    scale = jax.lax.optimization_barrier(x_scale * art.w_scale)
    y = yq.astype(jnp.float32) * (scale * (2.0 ** spec.drop_lsb))
    if art.comp_scale is not None:
        # drift compensation is a separate digital per-column multiply,
        # after the dequantize and before the offset-correction colsum (the
        # correction uses the time-invariant digital w_colsum, so only the
        # analog product gets rescaled).  The barrier pins it as its own
        # rounding step so eager/jit/shard_map stay bit-identical.
        y = jax.lax.optimization_barrier(y) * art.comp_scale
    return y


def programmed_linear(
    x: jnp.ndarray,
    art: ProgrammedLinear,
    interpret: Optional[bool] = None,
    colsum: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Signed-activation ``x @ w`` against a programmed artifact.

    The offset-encoding dance of ``models.layers.crossbar_linear`` — shift
    activations non-negative, run the unsigned datapath, correct digitally
    with the weight column sums — except the column sums come precomputed
    from the artifact (written once at programming time, as real hardware
    does) instead of a per-call ``sum(w, axis=0)`` reduction.

    ``colsum`` overrides ``art.w_colsum`` — the per-rank partial-sum path
    needs it: a contraction-sharded (K-sharded) artifact holds only this
    rank's rows, so the offset correction must use the *local* rows' column
    sums (the all-reduce of ``shift_r * colsum_r`` across ranks then
    reconstitutes the full correction exactly — offset encoding decomposes
    over row blocks).
    """
    shift = _over_range(jnp.min, x, art.spec)
    # barriers pin the rounding points of the offset-encode chain: without
    # them XLA is free to fuse the subtraction into the downstream quantize
    # divide (or the dequantize multiply and the correction into an FMA),
    # and those contractions round differently depending on how the
    # *surrounding* graph fuses — eager, jit and shard_map executions of the
    # same artifact must agree bit-for-bit (the distributed test tier pins
    # this across an 8-device mesh)
    xs = jax.lax.optimization_barrier((x - shift).astype(jnp.float32))
    y = programmed_matmul(xs, art, interpret=interpret)
    cs = art.w_colsum if colsum is None else colsum
    y, corr = jax.lax.optimization_barrier((y, shift.astype(jnp.float32) * cs))
    return y + corr


# ---------------------------------------------------------------------------
# Per-rank artifact sharding (mesh serving)
# ---------------------------------------------------------------------------
#
# A multi-chip deployment is a mapping constraint in the paper's sense: the
# weight's PartitionSpec says which crossbars live on which rank.  Artifacts
# must shard *with* the weights they shadow — same specs, sliced consistently
# across every array leaf — so a ``shard_map`` body can rebuild a rank-local
# ``ProgrammedLinear`` from rank-local array shards and serve programmed.
#
# Axis semantics per artifact field (w_codes is the weight, (…stack, K, N)):
#   * stacking axes (L layers / E experts) — slice every leaf; each (K, N)
#     slab stays intact, so expert-parallel serving is bit-identical;
#   * N (output columns) — column-separable: cells, colsums and gather
#     tables slice cleanly (``local_artifact`` re-indexes repair tables to
#     local column coordinates);
#   * K (contraction rows) — rank-local *rows of the global chip*: servable
#     as partial sums (quantization is elementwise in w, so sliced rows of
#     ``w_codes``/``g_eff`` ARE the rows the global chip programmed), but
#     ``w_colsum`` is a full-K reduction and cannot be sliced — the caller
#     must supply local column sums (``programmed_linear(colsum=...)``).


def _pspec_entries(wspec, ndim: int) -> Tuple[Any, ...]:
    """Normalize a PartitionSpec (possibly shorter than ndim) to entries."""
    entries = tuple(wspec) if wspec is not None else ()
    if len(entries) > ndim:
        raise ValueError(f"spec {wspec} longer than weight rank {ndim}")
    return entries + (None,) * (ndim - len(entries))


def artifact_shard_specs(art: ProgrammedLinear, wspec) -> Dict[str, Any]:
    """{array field: PartitionSpec} matching the shadowed weight's spec.

    ``wspec`` is the weight's PartitionSpec ((…stack, K, N) axes).  Every
    array leaf of the artifact gets the spec that slices it consistently
    with the weight: stacking axes map one-to-one, ``g_eff``/``g_spare``
    keep their bit-plane axis replicated, column-shaped leaves follow N.
    The returned dict is exactly what ``shard_map`` ``in_specs`` (via
    ``artifact_arrays``) or ``NamedSharding`` placement needs.
    """
    from jax.sharding import PartitionSpec as P

    nd = art.w_codes.ndim
    entries = _pspec_entries(wspec, nd)
    stack, kspec, nspec = entries[:-2], entries[-2], entries[-1]
    specs = {
        "w_codes": P(*stack, kspec, nspec),
        "g_eff": P(*stack, None, kspec, nspec),
        # w_colsum has no K axis — under K-sharding it stays the *global*
        # correction term (a K-sharded chip's per-rank partial colsums
        # cannot live in the artifact; the partial-sum serving path
        # overrides it via ``programmed_linear(colsum=)``)
        "w_colsum": P(*stack, nspec),
        "w_scale": P(*stack),
        "x_scale": P(*stack),
        # the spare block is a per-group column *budget*, not logical output
        # columns — keep it whole on every rank that holds the group's rows
        "g_spare": P(*stack, None, kspec, None),
        # (S, R, N) per-crossbar routing tables: slice/row-group axes stay
        # whole (they are physical-array coordinates), columns follow N
        "out_gather": P(*stack, None, None, nspec),
        # digital per-column compensation scales follow the output columns,
        # exactly like w_colsum
        "comp_scale": P(*stack, nspec),
    }
    return {f: specs[f] for f in ARTIFACT_ARRAY_FIELDS if getattr(art, f) is not None}


def dividing_pspec(spec, shape, axis_sizes) -> Any:
    """Degrade non-dividing PartitionSpec entries to replicated.

    The one shared rule for "can this dim actually shard here": an entry
    is kept only if every named axis exists in ``axis_sizes`` (a mesh's
    ``.shape`` mapping) and the axes' total size divides the dim; anything
    else becomes None.  ``shard_artifacts`` placement, checkpoint
    ``restore_programmed`` re-placement and ``local_artifact`` slicing all
    route through this, so a chip is re-placed on restore exactly where
    the deployment put it — the three sites can never drift apart.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    fixed = []
    for dim, ax in zip(shape, _pspec_entries(spec, len(shape))):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if any(a not in axis_sizes for a in axes):
            fixed.append(None)
            continue
        size = int(np.prod([axis_sizes[a] for a in axes]))
        fixed.append(ax if dim % size == 0 else None)
    return P(*fixed)


def artifact_arrays(art: ProgrammedLinear) -> Dict[str, jnp.ndarray]:
    """{field: array} for every non-None array leaf (shard_map input tree)."""
    return {
        f: getattr(art, f)
        for f in ARTIFACT_ARRAY_FIELDS
        if getattr(art, f) is not None
    }


def with_arrays(template: ProgrammedLinear, arrays: Dict[str, jnp.ndarray]) -> ProgrammedLinear:
    """Rebuild an artifact from (rank-local) arrays + a template's static aux.

    The inverse of ``artifact_arrays``: the ``shard_map`` body receives the
    sliced arrays as inputs, closes over the global artifact as the aux
    template, and rebinds.  Reports describe the *global* chip and are
    dropped — a rank-local view must not masquerade as the full record.
    """
    missing = {
        f: None for f in ARTIFACT_ARRAY_FIELDS if f not in arrays
    }
    return dataclasses.replace(
        template, report=None, repair=None, **arrays, **missing
    )


def shard_artifacts(prog: "ProgrammedModel", mesh, specs: Dict[str, Any]) -> "ProgrammedModel":
    """Place every artifact's arrays on ``mesh`` with its weight's spec.

    ``specs`` maps canonical artifact names to the shadowed weight's
    PartitionSpec (missing names stay replicated).  Non-dividing dims fall
    back to replicated per entry — mirroring ``layers.named_sharding_tree``
    — so a spec tuned for the production mesh degrades gracefully on a
    smaller test mesh.  Returns a new ProgrammedModel (same tree layout,
    same aux); under jit/GSPMD the placed arrays serve distributed instead
    of replicating the 8x ``g_eff`` planes onto every device, and a
    ``shard_map`` body receiving them with matching in_specs pays no
    resharding.
    """
    from jax.sharding import NamedSharding

    def _place(name: str, art: ProgrammedLinear) -> ProgrammedLinear:
        wspec = specs.get(name)
        if wspec is None:
            return art
        child_specs = artifact_shard_specs(art, wspec)
        placed = {
            f: jax.device_put(
                getattr(art, f),
                NamedSharding(
                    mesh,
                    dividing_pspec(
                        child_specs[f], getattr(art, f).shape, mesh.shape
                    ),
                ),
            )
            for f in child_specs
        }
        return dataclasses.replace(art, **placed)

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        prog.artifacts, is_leaf=lambda x: isinstance(x, ProgrammedLinear)
    )
    leaves = [
        _place(join_path(path), leaf) if isinstance(leaf, ProgrammedLinear) else leaf
        for path, leaf in flat
    ]
    return ProgrammedModel(jax.tree_util.tree_unflatten(treedef, leaves))


def local_artifact(
    art: ProgrammedLinear,
    wspec,
    axis_sizes: Dict[str, int],
    coords: Dict[str, int],
) -> ProgrammedLinear:
    """Materialize one rank's slice of an artifact (host-side, numpy).

    ``axis_sizes`` gives the mesh extent of every named axis in ``wspec``;
    ``coords`` is this rank's coordinate per axis.  Every array leaf is
    sliced along the weight's sharded axes; when N (output columns) is
    sharded and the artifact carries repair tables, ``out_gather`` is
    re-indexed to *local* column coordinates and ``g_spare`` is compacted to
    the spares local columns actually use — the per-rank hardware record a
    physically partitioned deployment would hold.  This is the validation /
    persistence counterpart of ``shard_artifacts`` (which places global
    arrays); serving correctness never depends on it because ``g_eff``
    already holds the repaired layout.
    """
    import numpy as np

    child_specs = artifact_shard_specs(art, wspec)

    def _block(entry, dim: int):
        # entry comes pre-normalized through dividing_pspec: non-dividing
        # or unknown-axis entries are already None (replicated)
        if entry is None:
            return slice(None)
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([axis_sizes[a] for a in axes]))
        idx = 0
        for a in axes:  # row-major linearization, like mesh device order
            idx = idx * axis_sizes[a] + coords[a]
        step = dim // size
        return slice(idx * step, (idx + 1) * step)

    def _slice(a, spec):
        a = np.asarray(jax.device_get(a))
        fixed = dividing_pspec(spec, a.shape, axis_sizes)
        sl = tuple(_block(e, d) for e, d in zip(fixed, a.shape))
        return a[sl]

    arrays = {f: _slice(getattr(art, f), child_specs[f]) for f in child_specs}
    # repair re-indexing keys off the *normalized* N entry: if the column
    # dim could not shard (axis unknown / non-dividing), out_gather was not
    # sliced above and must keep its global coordinates
    nspec = tuple(dividing_pspec(wspec, art.w_codes.shape, axis_sizes))[-1]
    if nspec is not None and art.out_gather is not None:
        n_cols = int(art.w_codes.shape[-1])
        size = int(np.prod([axis_sizes[a] for a in (nspec if isinstance(nspec, tuple) else (nspec,))]))
        n_loc = n_cols // size
        gather = arrays["out_gather"]  # stack + (S, R, n_loc)
        lead = gather.shape[:-3]
        gather = gather.reshape((-1,) + gather.shape[-3:]).copy()
        spare = arrays["g_spare"]  # stack + (S, K, B)
        spare2 = spare.reshape((-1,) + spare.shape[-3:])
        new_spares = []
        for i in range(gather.shape[0]):
            # one chip: compact its spare block to the columns any of the
            # per-(slice, row group) routing tables actually reference,
            # sharing one local numbering across all of them (a spare is one
            # physical column position in every array of the group)
            flat = gather[i].reshape(-1, gather.shape[-1])
            used: list = []
            for u in range(flat.shape[0]):
                for j in range(n_loc):
                    g = int(flat[u, j])
                    if g < n_cols:
                        # data column: repair only ever redirects a column to
                        # a spare, so the global value is this column's own
                        # physical position — locally that is just j
                        flat[u, j] = j
                    else:
                        b = g - n_cols
                        if b not in used:
                            used.append(b)
                        flat[u, j] = n_loc + used.index(b)
            new_spares.append(spare2[i][..., used] if used else spare2[i][..., :0])
        width = max((s.shape[-1] for s in new_spares), default=0)
        padded = [
            np.pad(s, [(0, 0)] * (s.ndim - 1) + [(0, width - s.shape[-1])])
            for s in new_spares
        ]
        spare_out = np.stack(padded).reshape(lead + padded[0].shape) if lead else padded[0]
        arrays["out_gather"] = gather.reshape(lead + gather.shape[-3:])
        arrays["g_spare"] = spare_out
    arrays = {f: jnp.asarray(v) for f, v in arrays.items()}
    return with_arrays(art, arrays)


# ---------------------------------------------------------------------------
# Name-keyed artifact binding (eager and under jit)
# ---------------------------------------------------------------------------
#
# Artifacts are addressed by the *joined parameter path* — "stage0/b0/mixer/
# wq" — never by array object identity.  Identity keying silently orphans
# every artifact the moment the params tree is copied (jax.device_put, buffer
# donation, an optimizer step, a checkpoint restore all produce fresh leaf
# objects), downgrading the whole model to plain XLA matmul with no error.
# Names survive all of those, survive jit retraces, and give transposed
# views (the tied LM head) something stable to bind to.

_SCOPE = threading.local()  # .stack: list[str] — the active module path


@contextlib.contextmanager
def name_scope(name: str):
    """Push one path component onto the ambient parameter-name scope.

    ``models.model`` pushes "stage{i}" / "b{i}" / "mixer" / "ffn" as it
    descends, so a call site only states its local leaf name —
    ``crossbar_linear(x, w, name="wq")`` — and ``scoped_name`` joins the
    full key.  Purely a Python-level dynamic scope: it is active during
    tracing, costs nothing inside the compiled computation, and nests
    across ``jit`` / ``scan`` / ``checkpoint`` bodies.
    """
    stack = getattr(_SCOPE, "stack", None)
    if stack is None:
        stack = _SCOPE.stack = []
    stack.append(str(name))
    try:
        yield
    finally:
        stack.pop()


def scoped_name(name: str) -> str:
    """Join ``name`` onto the active scope: the canonical artifact key."""
    return "/".join(getattr(_SCOPE, "stack", []) + [str(name)])


def _path_component(entry: Any) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def join_path(path: Tuple[Any, ...]) -> str:
    """Canonical "a/b/c" key for a jax tree path (Dict/Sequence/Attr keys)."""
    return "/".join(_path_component(p) for p in path)


def artifact_names(artifacts: Any, prefix: str = "") -> Dict[str, "ProgrammedLinear"]:
    """Flatten an artifact (sub)tree into {joined path: artifact}.

    ``prefix`` (usually the ambient scope at bind time) is prepended to
    every key, so a subtree bound deep inside a model maps to the same
    canonical names ``program_model`` derived from the full params tree.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(
        artifacts, is_leaf=lambda x: isinstance(x, ProgrammedLinear)
    )
    out: Dict[str, ProgrammedLinear] = {}
    for path, art in flat:
        if not isinstance(art, ProgrammedLinear):
            continue
        rel = join_path(path)
        key = "/".join(p for p in (prefix, rel) if p)
        out[key] = art
    return out


# Consumption accounting: every crossbar_linear call that *serves* from an
# artifact records the canonical name it resolved.  Together with the miss
# counter (models.layers) this gives the structural name-set check: after a
# traced forward, the names a ProgrammedModel emitted must equal the names
# the model consumed — a renamed layer or an artifact nothing serves is
# caught as a set mismatch even when no lookup ever *misses* (an orphaned
# artifact produces zero misses; only the consumption side exposes it).
# Recorded at trace time, bounded by distinct names, thread-local like the
# miss counter.
_CONSUMED = threading.local()  # .names: dict[str, None] (insertion-ordered set)


def record_artifact_consumed(name: str) -> None:
    names = getattr(_CONSUMED, "names", None)
    if names is None:
        names = _CONSUMED.names = {}
    names[name] = None


def consumed_artifact_names() -> Tuple[str, ...]:
    """Canonical names served from artifacts since the last reset, in
    first-consumption order."""
    return tuple(getattr(_CONSUMED, "names", {}))


def reset_consumed_artifact_names() -> None:
    _CONSUMED.names = {}


_BIND = threading.local()  # .maps: list of {name -> ProgrammedLinear}


@contextlib.contextmanager
def _push_bind_map(m: Dict[str, "ProgrammedLinear"]):
    stack = getattr(_BIND, "maps", None)
    if stack is None:
        stack = _BIND.maps = []
    stack.append(m)
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def bind_artifacts(artifacts: Any):
    """Bind a (sub)tree of artifacts by name for the dynamic scope.

    Keys are the subtree's own paths joined under the *current*
    ``name_scope`` — so ``model._run_stage``'s layer scan, which executes
    its body under ``name_scope("stage{i}")``, binds each per-iteration
    artifact slice to exactly the key the call sites inside the layer will
    ask for.  Later binds shadow earlier ones (innermost wins), which is
    how a per-expert slice bound inside the MoE expert scan takes
    precedence over the still-stacked per-layer binding outside it.
    """
    if artifacts is None:
        yield
        return
    m = artifact_names(artifacts, prefix="/".join(getattr(_SCOPE, "stack", [])))
    with _push_bind_map(m):
        yield


def active_artifact_for(
    name: str, shape: Optional[Tuple[int, ...]] = None
) -> Optional[ProgrammedLinear]:
    """Artifact bound to this canonical name in the dynamic scope, if any.

    Consulted by ``crossbar_linear`` (which passes the weight's shape).
    The shape guard does double duty: it rejects a still-stacked artifact
    when a 2-D weight asks (the enclosing scan hasn't sliced it yet — keep
    looking at outer binds), and it rejects stale bindings when two
    different tensors legitimately share a name (e.g. the embedding table
    vs its transposed LM-head artifact under the tied-head scheme).
    """
    for m in reversed(getattr(_BIND, "maps", [])):
        art = m.get(name)
        if art is not None and (shape is None or art.shape == tuple(shape)):
            return art
    return None


# The projection leaves routed through models.layers.crossbar_linear — the
# call sites that can consume an artifact: attention q/k/v/o, the MLA kv
# down-projection and its per-head absorbed up-projections, the dense-MLP
# wi/wo, the MoE expert bank wi/wg/wo plus router and shared-expert
# projections, and the untied LM head.  (A tied LM head serves from the
# transposed embedding artifact that ``program_model(tie_lm_head=True)``
# compiles under the embedding's name.)
_CROSSBAR_CONSUMERS = (
    "wq", "wk", "wv", "wo", "w_kv_down", "w_uk", "w_uv", "wi", "head",
    "wg", "router", "shared_wi", "shared_wg", "shared_wo",
)
# the 4-D leaves whose second axis is experts (the others stack heads)
_EXPERT_BANKS = ("wi", "wg", "wo")


def _path_names(path: Tuple[Any, ...]) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "name", ""))) for p in path)


def _matmul_leaf(path: Tuple[Any, ...], leaf: Any) -> bool:
    """Default predicate: which param leaves go onto crossbars.

    Allowlist of the projection names ``crossbar_linear`` actually serves
    (attention q/k/v/o, the MLA kv down-projection and per-head W_uk/W_uv,
    dense-MLP wi/wo, MoE router/experts/shared experts, the untied LM head),
    as 2-D matrices, 3-D scan-stacked ``(L, K, N)``, or 4-D expert or head
    banks ``(L, E, K, N)``.
    An allowlist — rather than excluding known non-matmuls — keeps stacked
    per-layer *vectors* (ssm ``conv_b``, ``D_skip``: ``(L, din)`` after
    stacking, indistinguishable from a small weight matrix by shape alone)
    from being miscompiled into unusable artifacts, and avoids paying
    write-verify programming + 8x ``g_eff`` memory for leaves no crossbar
    call site consumes.  Override with ``leaf_filter`` for exotic layouts.
    """
    if not isinstance(leaf, jnp.ndarray) or leaf.ndim not in (2, 3, 4):
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    names = _path_names(path)
    return bool(names) and names[-1] in _CROSSBAR_CONSUMERS


def stacked_only(artifacts: Any) -> Any:
    """Prune non-stacked artifacts from a stage subtree.

    A stage's layer scan slices every artifact array on a leading layer
    axis; a 2-D artifact (scalar ``w_scale``) inside a stacked-stage
    subtree can never be sliced that way and would crash the scan — drop
    it (the weight simply falls back to the per-call path).
    """
    return jax.tree_util.tree_map(
        lambda a: a if isinstance(a, ProgrammedLinear) and a.stacked else None,
        artifacts,
        is_leaf=lambda x: isinstance(x, ProgrammedLinear),
    )


class ProgrammedModel:
    """A pytree of ProgrammedLinear artifacts mirroring a params pytree.

    The tree shape mirrors the params so stage subtrees can ride the layer
    scan; ``by_name`` is the canonical path-keyed table every lookup
    resolves through.  Nothing here references parameter *objects* — a
    ProgrammedModel built once serves any congruent params tree (copies,
    donated buffers, restored checkpoints) and survives every jit retrace.
    """

    def __init__(self, artifacts: Any):
        self.artifacts = artifacts
        self.by_name: Dict[str, ProgrammedLinear] = artifact_names(artifacts)

    def bind(self):
        """Bind every artifact by name for the dynamic scope (must be
        entered at top-level model scope, e.g. around a jitted forward).
        Pushes the precomputed ``by_name`` table directly — no per-call
        tree reflatten in the serving hot loop."""
        return _push_bind_map(self.by_name)

    def subtree(self, key: str) -> Any:
        """Artifact subtree for one top-level params key (e.g. "stage0")."""
        try:
            return self.artifacts[key]
        except (KeyError, TypeError, IndexError):
            return None

    def lookup(
        self, name: str, shape: Optional[Tuple[int, ...]] = None
    ) -> Optional[ProgrammedLinear]:
        """Artifact for a canonical name, optionally shape-checked."""
        art = self.by_name.get(name)
        if art is not None and (shape is None or art.shape == tuple(shape)):
            return art
        return None

    @property
    def n_compiled(self) -> int:
        return len(self.by_name)

    @property
    def emitted_names(self) -> frozenset:
        """The canonical name set ``program_model`` emitted — the contract a
        forward pass must consume exactly (``verify_consumed``)."""
        return frozenset(self.by_name)

    def verify_consumed(self, consumed: Optional[Any] = None) -> None:
        """Assert a traced forward consumed exactly the emitted name set.

        ``consumed`` defaults to the ambient consumption record
        (``consumed_artifact_names()`` since the last reset).  Raises
        ``LookupError`` on any emitted artifact no call site served —
        the drift mode the miss counter can *never* catch: a renamed layer
        (or a leaf_filter that compiles a dead leaf) produces an orphaned
        artifact and zero misses, because nothing ever looks its name up.
        Names consumed but not emitted are reported alongside (they come
        from ad-hoc ``bind_artifacts`` scopes and usually accompany a
        rename).
        """
        got = frozenset(consumed_artifact_names() if consumed is None else consumed)
        unconsumed = self.emitted_names - got
        unexpected = got - self.emitted_names
        if unconsumed:
            raise LookupError(
                "programmed-artifact name-set drift: "
                f"{len(unconsumed)}/{len(self.by_name)} emitted artifacts were "
                f"never consumed by the forward ({', '.join(sorted(unconsumed)[:5])}"
                + (", ..." if len(unconsumed) > 5 else "")
                + ")"
                + (
                    f"; consumed-but-not-emitted: {', '.join(sorted(unexpected)[:5])}"
                    if unexpected
                    else ""
                )
                + " — a layer was renamed, or program_model compiled a leaf "
                "no call site serves."
            )

    def reports(self) -> Dict[str, ProgramReport]:
        """Name -> write-verify report for every compiled leaf that has one."""
        return {
            name: art.report
            for name, art in self.by_name.items()
            if art.report is not None
        }

    def repair_reports(self) -> Dict[str, Any]:
        """Name -> spare-column ``RepairReport`` (or per-layer tuple for
        stacked leaves) for every compiled leaf that was repaired."""
        return {
            name: art.repair
            for name, art in self.by_name.items()
            if art.repair is not None
        }

    def map_artifacts(
        self, fn: Callable[[ProgrammedLinear], ProgrammedLinear]
    ) -> "ProgrammedModel":
        """A new ProgrammedModel with ``fn`` applied to every artifact."""
        mapped = jax.tree_util.tree_map(
            lambda a: fn(a) if isinstance(a, ProgrammedLinear) else a,
            self.artifacts,
            is_leaf=lambda x: isinstance(x, ProgrammedLinear),
        )
        return ProgrammedModel(mapped)

    @property
    def t_service_s(self) -> float:
        """Fleet service time: the oldest chip's clock (chips age together
        under ``age``/``at_time``, so normally they all agree)."""
        return max((a.t_service_s for a in self.by_name.values()), default=0.0)

    def age(self, dt_s: float) -> "ProgrammedModel":
        """Every chip advanced ``dt_s`` seconds of service (no reprogramming)."""
        return self.map_artifacts(lambda a: age_artifact(a, dt_s))

    def at_time(self, t_s: float) -> "ProgrammedModel":
        """Every chip at absolute service time ``t_s`` (see ``artifact_at_time``)."""
        return self.map_artifacts(lambda a: artifact_at_time(a, t_s))


def program_model(
    params: Any,
    spec: CrossbarSpec = DEFAULT_SPEC,
    device: Optional[dm.DeviceConfig] = None,
    adc_cfg: Optional[ADCConfig] = SAFE_ADAPTIVE,
    *,
    fast: bool = True,
    with_report: bool = False,
    tie_lm_head: bool = False,
    leaf_filter: Optional[Callable[[Tuple[Any, ...], Any], bool]] = None,
    expert_chips: Optional[Tuple[int, ...]] = None,
    plan: Optional[Any] = None,
) -> ProgrammedModel:
    """Walk a param pytree and compile every matmul-shaped leaf.

    The whole-model programming pass: one ``program_layer`` per selected
    leaf, so an inference run (or a serving engine) works against a single
    fixed programmed chip.  ``leaf_filter(path, leaf) -> bool`` overrides
    the default projection-name predicate.

    ``expert_chips`` gives every 4-D expert bank one chip identity per
    expert (``program_layer(chips=...)``): an EP deployment that places
    expert ``e`` on rank ``e`` then models genuine chip-to-chip spread —
    each rank's slab drew its own device perturbations.  Leaves without an
    expert axis (2-D / 3-D) keep the base device unchanged, so the knob is
    a no-op for dense models and bit-compatible when ``None``.

    ``tie_lm_head=True`` additionally compiles the **transpose** of every
    2-D ``tokens`` embedding leaf and binds it to the embedding's own name
    — the tied LM head (``x @ tokens.T``) then serves from one artifact
    programmed at deploy time instead of reprogramming the transpose in
    every decode step (name-keyed binding is what makes this possible: a
    per-call transpose has no stable object identity, but it does have a
    name).  The (D, V) artifact shares the key with the (V, D) embedding
    leaf; shape-checked lookup keeps the two uses apart.

    ``plan`` (a ``core.planner.ChipPlan``, e.g. from ``planner.plan_model``
    on the same params) compiles each leaf under its per-layer
    ``LayerPlan``, matched by canonical artifact name; leaves the plan does
    not cover compile homogeneous, exactly as with ``plan=None``.
    """
    pred = leaf_filter if leaf_filter is not None else _matmul_leaf

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    arts = []
    for path, leaf in flat:
        action = _program_action(path, leaf, pred, tie_lm_head)
        chips = (
            expert_chips
            if (
                expert_chips is not None
                and action is not None
                and getattr(leaf, "ndim", 0) == 4
                and _path_names(path)[-1] in _EXPERT_BANKS
            )
            else None
        )
        layer_plan = (
            plan.layer_for(join_path(path))
            if plan is not None and action is not None
            else None
        )
        arts.append(
            program_layer(
                leaf.T if action == "transpose" else leaf,
                spec, device, adc_cfg, fast=fast, with_report=with_report,
                chips=chips, plan=layer_plan,
            )
            if action is not None
            else None
        )
    artifacts = jax.tree_util.tree_unflatten(treedef, arts)
    return ProgrammedModel(artifacts)


def _program_action(path, leaf, pred, tie_lm_head: bool) -> Optional[str]:
    """What ``program_model`` does with this param leaf: "program" the leaf,
    "transpose" it first (tied-head ``tokens`` embeddings), or None when it
    stays digital.  A pure decision — nothing is materialized, so shape-only
    consumers (``expected_artifact_names``) stay allocation-free."""
    names = _path_names(path)
    if (
        tie_lm_head
        and names
        and names[-1] == "tokens"
        and isinstance(leaf, jnp.ndarray)
        and leaf.ndim == 2
        and jnp.issubdtype(leaf.dtype, jnp.floating)
    ):
        return "transpose"
    if pred(path, leaf):
        return "program"
    return None


def expected_artifact_names(
    params: Any,
    *,
    tie_lm_head: bool = False,
    leaf_filter: Optional[Callable[[Tuple[Any, ...], Any], bool]] = None,
) -> Dict[str, Tuple[int, ...]]:
    """{canonical name: servable shape} ``program_model`` would compile —
    without programming anything.

    The validation counterpart of ``program_model``: a restored artifact
    store can be cross-checked against the model it is about to serve
    (``ServingEngine(restore_artifacts=...)`` does) so a stale or
    mismatched store fails loudly at construction instead of silently
    degrading every lookup to per-call programming.
    """
    pred = leaf_filter if leaf_filter is not None else _matmul_leaf
    out: Dict[str, Tuple[int, ...]] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        action = _program_action(path, leaf, pred, tie_lm_head)
        if action is not None:
            shape = tuple(leaf.shape)
            out[join_path(path)] = (
                tuple(reversed(shape)) if action == "transpose" else shape
            )
    return out
