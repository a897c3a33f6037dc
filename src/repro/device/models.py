"""Memristor device non-ideality models for the Newton crossbar datapath.

Composable, seeded models of everything between "the mapper assigns cell code
``c``" and "the column ADC samples a current":

* **conductance quantization** — a cell stores one of ``2**cell_bits`` levels
  spread linearly over the device rails ``[g_off_s, g_on_s]`` (the AG2048
  metal-oxide device range, 3.16 uS .. 316 uS),
* **programming variation** — each write lands lognormally distributed around
  the target conductance (``sigma`` on ``ln G``),
* **drift** — programmed conductance decays as the power law
  ``G(t) = G0 * (1 + t/t0)**-nu`` (PCM/ReRAM retention),
* **stuck-at faults** — a seeded per-cell map pins faulty cells to the
  ``g_on_s`` / ``g_off_s`` rails regardless of writes,
* **IR drop** — wordline/bitline wire resistance attenuates each cell's
  contribution.  This follows the AG2048 ``LineResistanceCrossbar`` model
  reduced to its first-order series-resistance form (``g_eff = g / (1 + g *
  R_series)`` with ``R_series`` the wire path through column ``j`` and row
  ``i`` of the 128-row group) so it stays a closed-form jnp expression
  instead of a nodal solve.

All randomness flows from ``DeviceConfig.seed`` through stage-tagged
``jax.random.fold_in`` keys, so fault maps and programming noise are
reproducible functions of (config, weight-slab shape).  The all-default
``DeviceConfig()`` is the identity: effective cell codes equal the ideal
slices bit-for-bit (tests/test_device.py pins this down).

Effective cell values are returned in *code units* on a ``2**-GEFF_FRAC_BITS``
grid.  The grid is what makes the noisy Pallas kernel verifiable: every
column partial is a multiple of the grid step and bounded by
``spec.partial_max``, so float32 summation is exact in any order and the
kernel matches the jnp reference bit-for-bit, not just allclose
(see ``kernels/noisy_vmm.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fixedpoint as fxp
from repro.core.crossbar import CrossbarSpec

# Fractional bits of the effective-cell-code grid.  Exactness of f32 column
# sums needs partial_max * 2**GEFF_FRAC_BITS < 2**24 (float32 integer range):
# 384 * 256 = 98304 for the default spec, with ample headroom for variants.
GEFF_FRAC_BITS = 8

# Stage-key registry: every independent randomness stream in the programming
# pipeline is named here, once, with a distinct fold_in index.  Call sites
# MUST use these constants (never string literals) — `repro.analysis`'s
# stage-key collision rule enforces both halves statically: duplicate indices
# here would correlate supposedly independent draws, and an ad-hoc literal at
# a call site would dodge the registry.
STAGE_FAULTS = "faults"
STAGE_PROGRAM = "program"
STAGE_SPARE_FAULTS = "spare_faults"
STAGE_SPARE_PROGRAM = "spare_program"

_STAGES = {
    STAGE_FAULTS: 0,
    STAGE_PROGRAM: 1,
    STAGE_SPARE_FAULTS: 2,
    STAGE_SPARE_PROGRAM: 3,
}


@dataclasses.dataclass(frozen=True)
class DeviceConfig:
    """Programmed-conductance non-ideality knobs (all default to ideal).

    ``spare_cols`` provisions redundant spare columns — per 128-column
    crossbar column group — for the fault-aware repair planner
    (``device.repair``): at programming time the worst fault-afflicted
    columns of a weight slab are remapped into spares drawn from their own
    seeded fault/variation fields.  Zero (the default) disables repair.

    ``temp_k`` / ``drift_ea_ev`` make retention drift temperature-dependent:
    the power-law exponent is scaled Arrhenius-style,
    ``nu(T) = drift_nu * exp((Ea/kB) * (1/T_ref - 1/T))`` with
    ``T_ref = 300 K`` — a hotter chip ages faster.  ``drift_ea_ev = 0`` (the
    default) keeps drift temperature-independent bit-for-bit, so every
    pre-existing config is unchanged.  (The AG2048 calibration folds
    temperature into ``sigma``; this knob unfolds the retention component.)

    ``chip`` is a physical chip identity mixed into every seeded draw
    (faults, programming variation): two crossbars holding *identical*
    weight slabs on the same ``seed`` draw identical non-idealities — fine
    for one die, wrong for a fleet.  Giving each rank of a sharded
    deployment its own ``chip`` index models chip-to-chip spread; ``chip=0``
    (the default) reproduces the single-die draws bit-for-bit.
    """

    sigma: float = 0.0  # lognormal programming variation of ln(G)
    p_stuck_on: float = 0.0  # fraction of cells pinned at g_on_s
    p_stuck_off: float = 0.0  # fraction of cells pinned at g_off_s
    drift_nu: float = 0.0  # power-law drift exponent
    t_drift_s: float = 0.0  # time since programming (seconds)
    t0_s: float = 1.0  # drift reference time
    r_line_ohm: float = 0.0  # wire resistance per cell segment
    g_on_s: float = 316e-6  # device rails (siemens); AG2048 static memristor
    g_off_s: float = 3.16e-6
    write_verify_iters: int = 1  # programming pulses (1 = open-loop write)
    write_verify_tol: float = 0.25  # verify tolerance, cell-code units
    spare_cols: int = 0  # spare columns per crossbar column group (repair)
    temp_k: float = 300.0  # operating temperature (drift Arrhenius scaling)
    drift_ea_ev: float = 0.0  # drift activation energy (eV); 0 = T-independent
    chip: int = 0  # physical chip identity (decorrelates fleet draws)
    seed: int = 0

    def replace(self, **kw) -> "DeviceConfig":
        return dataclasses.replace(self, **kw)

    @property
    def is_ideal(self) -> bool:
        return (
            self.sigma == 0.0
            and self.p_stuck_on == 0.0
            and self.p_stuck_off == 0.0
            and (self.drift_nu == 0.0 or self.t_drift_s == 0.0)
            and self.r_line_ohm == 0.0
        )


IDEAL_DEVICE = DeviceConfig()


def _stage_key(cfg: DeviceConfig, stage: str, tag: Optional[jnp.ndarray] = None) -> jax.Array:
    key = jax.random.PRNGKey(cfg.seed)
    if cfg.chip:
        # fold only a nonzero chip identity so chip=0 draws stay
        # bit-identical to every pre-fleet config (tests pin this)
        key = jax.random.fold_in(key, cfg.chip)
    key = jax.random.fold_in(key, _STAGES[stage])
    if tag is not None:
        key = jax.random.fold_in(key, tag)
    return key


def _slab_tag(w_codes_biased: jnp.ndarray) -> jnp.ndarray:
    """Content-derived uint32 tag mixed into the stage keys per weight slab.

    Without it, every same-shape slab in a model (e.g. all q/k/v/o
    projections) would draw identical fault maps and noise fields from the
    shared ``DeviceConfig``, making layer errors add coherently instead of
    independently.  A position-weighted wrapping sum keeps the pipeline a
    deterministic function of (config, weights) while decorrelating slabs.
    """
    w = w_codes_biased.astype(jnp.uint32).ravel()
    mix = jnp.arange(w.size, dtype=jnp.uint32) * jnp.uint32(2654435761) + jnp.uint32(1)
    return jnp.sum(w * mix, dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Conductance <-> cell-code mapping (level quantization)
# ---------------------------------------------------------------------------

def code_step_siemens(spec: CrossbarSpec, cfg: DeviceConfig) -> float:
    """Conductance per cell-code LSB: rails split into 2**cell_bits levels."""
    return (cfg.g_on_s - cfg.g_off_s) / ((1 << spec.cell_bits) - 1)


def conductance_of_codes(codes: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig) -> jnp.ndarray:
    return cfg.g_off_s + codes.astype(jnp.float32) * code_step_siemens(spec, cfg)


def codes_of_conductance(g: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig) -> jnp.ndarray:
    return (g - cfg.g_off_s) / code_step_siemens(spec, cfg)


def quantize_code_grid(codes: jnp.ndarray) -> jnp.ndarray:
    """Snap effective codes to the 2**-GEFF_FRAC_BITS grid (see module doc)."""
    scale = float(1 << GEFF_FRAC_BITS)
    return jnp.round(codes * scale) / scale


# ---------------------------------------------------------------------------
# Stochastic / deterministic perturbation stages
# ---------------------------------------------------------------------------

def fault_masks(
    cfg: DeviceConfig,
    shape: Tuple[int, ...],
    tag: Optional[jnp.ndarray] = None,
    stage: str = STAGE_FAULTS,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Disjoint (stuck_on, stuck_off) bool maps — a pure function of
    (cfg, shape, tag): repeated calls (eager or under ``jax.jit``) return the
    identical draw.  ``tag`` decorrelates same-shape slabs (see
    ``_slab_tag``); ``stage`` selects an independent fault field — the
    repair planner draws its spare-column block from ``"spare_faults"`` so
    provisioning spares never perturbs the primary columns' faults."""
    u = jax.random.uniform(_stage_key(cfg, stage, tag), shape)
    stuck_off = u < cfg.p_stuck_off
    stuck_on = (u >= cfg.p_stuck_off) & (u < cfg.p_stuck_off + cfg.p_stuck_on)
    return stuck_on, stuck_off


def apply_faults(
    g: jnp.ndarray, masks: Tuple[jnp.ndarray, jnp.ndarray], cfg: DeviceConfig
) -> jnp.ndarray:
    stuck_on, stuck_off = masks
    return jnp.where(stuck_on, cfg.g_on_s, jnp.where(stuck_off, cfg.g_off_s, g))


def program_variation(g: jnp.ndarray, cfg: DeviceConfig, key: jax.Array) -> jnp.ndarray:
    """One write pulse: lands lognormally around the target (median-preserving)."""
    if cfg.sigma == 0.0:
        return g
    z = jax.random.normal(key, g.shape, jnp.float32)
    return g * jnp.exp(cfg.sigma * z)


# Boltzmann constant in eV/K and the reference temperature the AG2048
# drift exponent was calibrated at — ``effective_drift_nu`` is exactly
# ``drift_nu`` at 300 K (exp(0) == 1.0, bit-for-bit).
BOLTZMANN_EV_K = 8.617333262e-5
DRIFT_T_REF_K = 300.0


def effective_drift_nu(cfg: DeviceConfig) -> float:
    """Temperature-scaled drift exponent (Arrhenius in 1/T).

    ``nu(T) = drift_nu * exp((Ea/kB) * (1/T_ref - 1/T))``: retention loss is
    thermally activated, so a chip above the 300 K reference drifts faster
    and a cold one slower.  ``drift_ea_ev = 0`` or ``temp_k = 300`` return
    ``drift_nu`` unchanged (exactly — the scale factor is 1.0).
    """
    if cfg.drift_ea_ev == 0.0 or cfg.temp_k == DRIFT_T_REF_K:
        return cfg.drift_nu
    # a host constant even while a programming pass is being traced
    with jax.ensure_compile_time_eval():
        scale = float(
            jnp.exp(
                (cfg.drift_ea_ev / BOLTZMANN_EV_K)
                * (1.0 / DRIFT_T_REF_K - 1.0 / cfg.temp_k)
            )
        )
    return cfg.drift_nu * scale


def apply_drift(g: jnp.ndarray, cfg: DeviceConfig) -> jnp.ndarray:
    """Power-law retention loss; identity at t=0 or nu=0."""
    nu = effective_drift_nu(cfg)
    if nu == 0.0 or cfg.t_drift_s == 0.0:
        return g
    factor = (1.0 + cfg.t_drift_s / cfg.t0_s) ** (-nu)
    return g * factor


def drift_time_factor(cfg: DeviceConfig, t_from_s: float, t_to_s: float) -> float:
    """Incremental conductance decay between two *service* times.

    The power law is anchored at programming time: a chip programmed with
    baked-in drift ``t_drift_s`` and now ``t`` seconds into service sits at
    total elapsed time ``t_drift_s + t``, so the decay accrued between
    service times ``t1 < t2`` is the ratio

        ``((1 + (t_drift_s + t2)/t0) / (1 + (t_drift_s + t1)/t0)) ** -nu``

    — exactly 1.0 when nothing drifts (``nu == 0`` or ``t1 == t2``), which
    is what makes ``device.programmed`` aging a bit-identical no-op for
    drift-free configs.  Composable: ``f(t1,t2) * f(t2,t3) == f(t1,t3)`` up
    to float rounding, so repeated ``age()`` steps track ``at_time``.
    """
    nu = effective_drift_nu(cfg)
    if nu == 0.0 or t_to_s == t_from_s:
        return 1.0
    if t_to_s < t_from_s:
        raise ValueError(
            f"cannot run service time backwards: {t_to_s} < {t_from_s} "
            "(the fresh chip is gone; reprogram to rejuvenate)"
        )
    base = cfg.t_drift_s
    return float(
        ((1.0 + (base + t_to_s) / cfg.t0_s) / (1.0 + (base + t_from_s) / cfg.t0_s))
        ** (-nu)
    )


def age_effective_codes(
    codes: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig, factor: float
) -> jnp.ndarray:
    """Drift-evolve stored effective cell codes by a conductance decay factor.

    The stored codes are the grid-quantized read-time view of the cell
    conductances; aging maps them back through the level map
    (``g = g_off + c * step``), decays the conductance by ``factor`` — the
    power law acts on G, not on codes, so the code-space transform is the
    affine ``f*c + (f-1)*g_off/step``, not a pure scale — and re-reads
    through clip + grid quantization.  Exact (up to one re-quantization on
    the 2**-GEFF_FRAC_BITS grid) for the closed-form IR-drop-free read
    path; with line resistance it is the same first-order view the read
    pipeline already commits to.  ``factor == 1.0`` must be short-circuited
    by the caller — re-quantization is not a bit-exact identity.
    """
    step = code_step_siemens(spec, cfg)
    g = cfg.g_off_s + codes.astype(jnp.float32) * step
    aged = (g * factor - cfg.g_off_s) / step
    aged = jnp.clip(aged, 0.0, float((1 << spec.cell_bits) - 1))
    return quantize_code_grid(aged)


def ir_drop_conductance(
    g: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig, col_offset=0
) -> jnp.ndarray:
    """First-order line-resistance attenuation (AG2048 model, closed form).

    A cell at (row ``i`` of its 128-row group, column ``j``) sees series wire
    resistance ``(j + 1) * r`` along the wordline from the driver plus
    ``(rows - i) * r`` along the bitline down to the ADC; its effective
    conductance is the series combination ``g / (1 + g * R_series)``.  Cells
    far from driver and ADC attenuate most — the classic IR-drop corner.

    ``g``: (S, K, N) conductances; K is the contraction dim (wordlines, row
    ``i = k mod rows`` within its group), N the bitlines.  ``col_offset``
    (a scalar, or one value per column) shifts the wordline positions —
    ``device.repair`` reads each spare block at the position just past its
    own column group's data columns, not at the near-driver corner.
    """
    if cfg.r_line_ohm == 0.0:
        return g
    S, K, N = g.shape
    i = (jnp.arange(K, dtype=jnp.int32) % spec.rows).astype(jnp.float32)
    j = jnp.arange(N, dtype=jnp.float32) + jnp.asarray(col_offset, jnp.float32)
    r_series = ((j[None, :] + 1.0) + (spec.rows - i[:, None])) * cfg.r_line_ohm
    return g / (1.0 + g * r_series[None, :, :])


# ---------------------------------------------------------------------------
# Programming + read pipeline
# ---------------------------------------------------------------------------

def target_cell_codes(w_codes_biased: jnp.ndarray, spec: CrossbarSpec) -> jnp.ndarray:
    """(K, N) biased weight codes -> (S, K, N) ideal per-slice cell codes."""
    return fxp.cell_slices(w_codes_biased, spec.weight_bits, spec.cell_bits)


def program_attempt(
    target_g: jnp.ndarray,
    masks: Tuple[jnp.ndarray, jnp.ndarray],
    cfg: DeviceConfig,
    key: jax.Array,
    i: int,
) -> jnp.ndarray:
    """Write pulse ``i`` of a verify sequence: one noisy open-loop write with
    stuck cells pinned.  Per-pulse randomness is ``fold_in(key, i)`` — the
    shared currency between ``programmed_conductance`` (trace-safe inference
    path), ``program.write_verify`` (host-side reporting path) and the spare
    block programmer in ``device.repair``, which must all land bit-identical
    conductances for the same pulse index."""
    return apply_faults(
        program_variation(target_g, cfg, jax.random.fold_in(key, i)), masks, cfg
    )


def write_verify_fixed(
    target: jnp.ndarray,
    masks: Tuple[jnp.ndarray, jnp.ndarray],
    key: jax.Array,
    spec: CrossbarSpec,
    cfg: DeviceConfig,
) -> jnp.ndarray:
    """Fixed-iteration (trace-safe) write-verify of target cell codes.

    With ``write_verify_iters <= 1`` this is an open-loop write (one noisy
    pulse); otherwise cells whose read-back code is more than
    ``write_verify_tol`` from target are re-pulsed.  Stuck cells ignore
    every pulse.
    """
    target_g = conductance_of_codes(target, spec, cfg)
    iters = max(1, cfg.write_verify_iters)
    g = program_attempt(target_g, masks, cfg, key, 0)
    if iters > 1:
        done = (
            jnp.abs(codes_of_conductance(g, spec, cfg) - target) <= cfg.write_verify_tol
        )
        for i in range(1, iters):
            attempt = program_attempt(target_g, masks, cfg, key, i)
            g = jnp.where(done, g, attempt)
            done = (
                jnp.abs(codes_of_conductance(g, spec, cfg) - target) <= cfg.write_verify_tol
            )
    return g


def programmed_conductance(
    w_codes_biased: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig
) -> jnp.ndarray:
    """Program a weight slab into cell conductances (trace-safe).

    Draws the slab's fault map and pulse keys, then runs the fixed-iteration
    ``write_verify_fixed`` loop.  ``program.write_verify`` wraps the same
    keys with host-side convergence reporting.
    """
    target = target_cell_codes(w_codes_biased, spec)
    tag = _slab_tag(w_codes_biased)
    masks = fault_masks(cfg, target.shape, tag)
    key = _stage_key(cfg, STAGE_PROGRAM, tag)
    return write_verify_fixed(target, masks, key, spec, cfg)


def read_effective_codes(
    g: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig, col_offset=0
) -> jnp.ndarray:
    """Read-time view of programmed conductances, in grid-quantized code units.

    Applies drift and IR drop, converts back through the level map, clips to
    the physical rails [0, 2**cell_bits - 1] and snaps to the verification
    grid.  (S, K, N) in, (S, K, N) float32 out.  ``col_offset`` positions
    the block on the wordline for IR drop (see ``ir_drop_conductance``).
    """
    g = apply_drift(g, cfg)
    g = ir_drop_conductance(g, spec, cfg, col_offset=col_offset)
    codes = codes_of_conductance(g, spec, cfg)
    codes = jnp.clip(codes, 0.0, float((1 << spec.cell_bits) - 1))
    return quantize_code_grid(codes)


def wants_repair(cfg: DeviceConfig) -> bool:
    """Spare-column repair is active: a budget is provisioned and stuck-at
    faults exist to repair (variation/drift are not column-clustered, so
    repair without faults would be pure provisioning waste)."""
    return cfg.spare_cols > 0 and (cfg.p_stuck_on > 0.0 or cfg.p_stuck_off > 0.0)


def effective_cell_codes(
    w_codes_biased: jnp.ndarray,
    spec: CrossbarSpec,
    cfg: DeviceConfig,
    repair: bool = True,
) -> jnp.ndarray:
    """Full program+read pipeline: (K, N) biased codes -> (S, K, N) effective.

    The one call sites need: what the analog datapath actually multiplies
    against, given this device config.  Deterministic in (cfg, shape); the
    ideal config returns the exact integer slices.

    When the config provisions spare columns (``cfg.spare_cols > 0``) and
    stuck-at faults are enabled, the returned layout is the *repaired* one:
    ``device.repair`` remaps the worst fault-afflicted columns into
    programmed spares and scatters the spare cells back into the victim
    positions, so every downstream consumer (functional model, Pallas
    kernels, programmed artifacts) reads the repaired chip with zero
    steady-state overhead.  ``repair=False`` returns the primary columns
    only (``device.programmed`` uses this to record the spare block and
    gather map explicitly).
    """
    if cfg.is_ideal:
        return target_cell_codes(w_codes_biased, spec).astype(jnp.float32)
    g_eff, target, tag, masks = _programmed_effective(w_codes_biased, spec, cfg)
    if repair and wants_repair(cfg):
        from repro.device import repair as repair_mod  # deferred: repair imports models

        rplan = repair_mod.plan_repair(
            w_codes_biased, spec, cfg, target=target, tag=tag, primary_masks=masks
        )
        g_eff = repair_mod.apply_repair(g_eff, rplan)
    return g_eff


def _programmed_effective(
    w_codes_biased: jnp.ndarray, spec: CrossbarSpec, cfg: DeviceConfig
):
    """Programming pipeline with its intermediates exposed: (g_eff, target,
    tag, masks).  The repair planner needs the same target slices, slab tag
    and primary fault draw — handing them over avoids paying the cell-slice
    expansion / content hash / fault draw twice per slab."""
    target = target_cell_codes(w_codes_biased, spec)
    tag = _slab_tag(w_codes_biased)
    masks = fault_masks(cfg, target.shape, tag)
    key = _stage_key(cfg, STAGE_PROGRAM, tag)
    g = write_verify_fixed(target, masks, key, spec, cfg)
    return read_effective_codes(g, spec, cfg), target, tag, masks
