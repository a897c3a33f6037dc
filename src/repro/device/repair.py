"""Fault-aware spare-column repair of programmed crossbar slabs.

Newton's mapping (§III.B) provisions tiles as if every memristor cell works;
real arrays ship with stuck-at cells, and Xiao et al. ("On the Accuracy of
Analog Neural Network Inference Accelerators") show those hard faults — not
programming variation — dominate accuracy loss at realistic rates.  Because
the datapath is column-separable (one bitline = one output), the classic
memory-repair remedy applies: provision a budget of **redundant spare
columns** per crossbar and, at programming time, remap the worst
fault-afflicted columns into them, rerouting the column outputs through a
gather table.

The pipeline here:

* ``column_salience`` — rank columns by fault-weighted salience: the total
  |installed - target| cell-code error a column's stuck cells would cause,
  weighted by bit-slice significance ``2**(s * cell_bits)`` (a stuck MSB
  slice cell is 16384x a stuck LSB one for the default 16b/2b layout).
* ``plan_repair`` — greedy budget assignment at **physical-crossbar
  granularity**: each (bit-slice, row group) of a slab is its own 128x128
  array with its own ADC, and both the slice shift-and-add and the
  row-group accumulation happen digitally *after* conversion — so the
  output mux can pick primary-or-spare independently per (slice, row
  group, column), not just per whole logical column.  That granularity is
  load-bearing: at p = 1e-2 a 512-row x 8-slice logical column is faulty
  with near certainty (and so is any whole-column spare), while a single
  128-cell physical column is clean with probability ~0.28 — per-unit
  matching is what keeps deep slabs repairable.  Within each unit the
  greedy repeatedly moves the (victim, spare) pair with the largest
  salience *gain*.  Spares draw their own seeded stuck-at field (stage
  ``"spare_faults"``), so a faulty spare is never blindly trusted — a
  victim moves only where it strictly improves.  Trace-safe: the loop has
  a static trip count (the budget) and all choices are jnp argmax/where,
  vmapped over the slice x row-group units.
* spare programming — the chosen victims' target codes are written into the
  spare block through the same write-verify pulse pipeline as primary cells
  (stage ``"spare_program"`` keys), then read back through drift/IR-drop.
* ``apply_repair`` — scatter the programmed spare cells into the victim
  positions.  The datapath is column-separable, so pre-gathering the
  repaired layout at programming time is bit-identical to gathering kernel
  outputs at read time — and costs nothing per call: all three Pallas
  kernels consume the repaired ``(S, K, N)`` layout unchanged.

Primary columns are programmed exactly as without repair (their fault and
variation draws never see the spare block), so repair on/off comparisons are
apples-to-apples and a zero-fault config with a nonzero budget stays
bit-identical to the unrepaired path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.crossbar import CrossbarSpec
from repro.device import models as dm


def spare_budget(n_cols: int, spec: CrossbarSpec, cfg: dm.DeviceConfig) -> int:
    """Spare columns available to one (K, N) weight slab.

    ``cfg.spare_cols`` is provisioned per physical crossbar column group; a
    slab spanning ``ceil(N / spec.cols)`` column groups owns that many
    budgets, and each budget is group-local — a spare's output muxes can
    only stand in for columns of their own group (``plan_repair``).  (A
    spare is one redundant column position in every bit-slice x row-group
    crossbar of the group; each of those S x R physical spare columns is
    assigned its own victim independently, since the cross-array merge is
    digital.)
    """
    return int(cfg.spare_cols) * max(1, -(-n_cols // spec.cols))


def _slice_weights(spec: CrossbarSpec) -> jnp.ndarray:
    """(S,) bit-slice significance: slice s carries 2**(s * cell_bits)."""
    return (2.0 ** (spec.cell_bits * jnp.arange(spec.n_slices))).astype(jnp.float32)


def column_salience(
    target: jnp.ndarray,
    masks: Tuple[jnp.ndarray, jnp.ndarray],
    spec: CrossbarSpec,
) -> jnp.ndarray:
    """Fault-weighted salience of each column of a target-code slab.

    ``target``: (S, K, N) ideal cell codes; ``masks``: (stuck_on, stuck_off)
    bool maps of the same shape.  Returns (N,) float32: the significance-
    weighted total |stuck value - target| each column's hard faults inflict.
    A stuck-on cell installs the top code ``cell_max``; stuck-off installs 0.
    """
    stuck_on, stuck_off = masks
    cell_max = float((1 << spec.cell_bits) - 1)
    w = _slice_weights(spec)[:, None, None]
    err = jnp.where(stuck_on, (cell_max - target) * w, 0.0)
    err = err + jnp.where(stuck_off, target * w, 0.0)
    return jnp.sum(err, axis=(0, 1)).astype(jnp.float32)


def _unit_view(a: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(S, K, X) -> (S, R, rows, X) physical-crossbar units, zero-padding a
    partial last row group (padded cells carry target 0 and no faults, so
    they never contribute salience or spare error)."""
    S, K, X = a.shape
    R = -(-K // rows)
    pad = R * rows - K
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
    return a.reshape(S, R, rows, X)


def _unit_fault_error(
    target_u: jnp.ndarray,
    masks_u: Tuple[jnp.ndarray, jnp.ndarray],
    spec: CrossbarSpec,
) -> jnp.ndarray:
    """(S, R, N) unweighted per-unit fault error of a unit view: the total
    |stuck - target| cell error each physical column's hard faults inflict
    (slice significance is a *cross*-unit weight and does not reorder
    choices within one slice's crossbar)."""
    cell_max = float((1 << spec.cell_bits) - 1)
    err = jnp.where(masks_u[0], cell_max - target_u, 0.0)
    err = err + jnp.where(masks_u[1], target_u, 0.0)
    return jnp.sum(err, axis=2).astype(jnp.float32)


@dataclasses.dataclass
class RepairPlan:
    """Trace-safe record of one slab's spare-column repair.

    Repair is resolved per physical crossbar: with ``R = ceil(K / rows)``
    row groups and ``S`` bit slices, every (s, r) pair is its own array and
    gets its own victim/gather tables.  ``victim``: (S, R, B) int32 — the
    logical column whose (s, r) unit is programmed into each spare column's
    (s, r) unit, -1 for unused slots.  ``out_gather``: (S, R, N) int32 —
    physical column serving each logical output within that crossbar
    (j itself, or N + b for repaired units); the routing tables a real chip
    would burn into its per-array column muxes (the merge across slices and
    row groups is digital, so per-array muxing costs nothing extra).
    ``g_spare``: (S, K, B) float32 effective cell codes of the programmed
    spare block; slots not serving a victim are programmed toward target 0
    but still read back their own faults/variation, so detect them via
    ``victim == -1``, not zero cells.  ``rows`` is the unit height (the
    physical crossbar row count the plan was built for).  Saliences are
    pre/post-repair (N,) vectors of ``column_salience`` units.
    """

    victim: jnp.ndarray
    out_gather: jnp.ndarray
    g_spare: jnp.ndarray
    salience_before: jnp.ndarray
    salience_after: jnp.ndarray
    rows: int = 128


# a pytree, so a jitted programming pass can return the plan
jax.tree_util.register_dataclass(
    RepairPlan,
    data_fields=["victim", "out_gather", "g_spare", "salience_before", "salience_after"],
    meta_fields=["rows"],
)


@dataclasses.dataclass(frozen=True)
class RepairReport:
    """Host-side summary of a ``RepairPlan`` (hashable: rides pytree aux).

    ``budget`` and ``n_repaired`` count (slice, row group, spare) *unit
    slots* — the per-physical-crossbar repair resolution; ``repaired_cols``
    is the sorted set of logical columns with at least one repaired unit.
    """

    budget: int
    n_repaired: int
    repaired_cols: Tuple[int, ...]  # logical columns with >= 1 repaired unit
    salience_before: float
    salience_after: float

    @property
    def recovered_frac(self) -> float:
        """Fraction of planner-model salience removed by the repair."""
        if self.salience_before <= 0.0:
            return 0.0
        return 1.0 - self.salience_after / self.salience_before


def _greedy_assign(sal0: jnp.ndarray, err_sp: jnp.ndarray):
    """Greedy (victim, spare) assignment within one column group.

    Each of the ``B`` steps moves the pair with the largest remaining
    salience gain, if any strict improvement exists.  A repaired column is
    never displaced to a second spare: re-stealing column j from spare b1
    by b2 would need ``err_sp[b2, j] < err_sp[b1, j]``, but b2 was already
    available when (b1, j) won the argmax (the available set only shrinks),
    so ``err_sp[b1, j] <= err_sp[b2, j]`` — every spare therefore serves at
    most one column and no victim slot is ever orphaned.  Returns local
    (salience_after (n,), victim (B,), gather (n,)) with gather entries
    ``>= n`` meaning "spare gather - n".
    """
    B, n = err_sp.shape

    def _step(_, carry):
        sal, victim, gather, avail = carry
        gain = jnp.where(avail[:, None], sal[None, :] - err_sp, -jnp.inf)
        flat = jnp.argmax(gain)
        b, j = flat // n, flat % n
        do = gain.reshape(-1)[flat] > 0.0
        victim = victim.at[b].set(jnp.where(do, j.astype(jnp.int32), victim[b]))
        gather = jnp.where(do, gather.at[j].set(n + b.astype(jnp.int32)), gather)
        sal = sal.at[j].set(jnp.where(do, err_sp[b, j], sal[j]))
        avail = avail.at[b].set(jnp.where(do, False, avail[b]))
        return sal, victim, gather, avail

    sal, victim, gather, _ = jax.lax.fori_loop(
        0,
        B,
        _step,
        (
            sal0,
            jnp.full((B,), -1, jnp.int32),
            jnp.arange(n, dtype=jnp.int32),
            jnp.ones((B,), bool),
        ),
    )
    return sal, victim, gather


def plan_repair(
    w_codes_biased: jnp.ndarray,
    spec: CrossbarSpec,
    cfg: dm.DeviceConfig,
    *,
    target: Optional[jnp.ndarray] = None,
    tag: Optional[jnp.ndarray] = None,
    primary_masks: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> Optional[RepairPlan]:
    """Plan and program one slab's spare-column repair (trace-safe).

    Planning is *per column group and per physical crossbar*: a spare
    column lives in one 128-column crossbar group and its per-array output
    muxes can only stand in for columns of that group, so each group's
    ``cfg.spare_cols`` spares are assigned greedily among its own
    <= ``spec.cols`` columns — independently for every (bit-slice, row
    group) unit, since each is its own array and the cross-array merge is
    digital.  (This also bounds the planner: every gain matrix is at most
    ``spare_cols x cols``, vmapped over the S x R x group units, so wide
    slabs — e.g. a vocab-sized LM head — cost one small greedy pass per
    group instead of one quadratic pass over all columns, in a program
    whose size does not grow with the number of groups.)  Spares carry their
    own seeded stuck-at faults, write-verify pulse noise, drift and IR
    drop, so the plan never pretends a spare is perfect.  Returns None when
    the config provisions no repair.

    ``target`` / ``tag`` / ``primary_masks`` let a caller that has already
    run the programming pipeline for this slab (``effective_cell_codes``)
    hand its intermediates over instead of paying the cell-slice expansion,
    content-hash and fault draw a second time; when provided they MUST be
    the values the standard pipeline derives from ``w_codes_biased``.
    """
    if not dm.wants_repair(cfg):
        return None
    if target is None:
        target = dm.target_cell_codes(w_codes_biased, spec)
    target = target.astype(jnp.float32)
    S, K, N = target.shape
    R = -(-K // spec.rows)
    B_per = int(cfg.spare_cols)
    B = spare_budget(N, spec, cfg)
    n_groups = B // B_per
    if tag is None:
        tag = dm._slab_tag(w_codes_biased)
    if primary_masks is None:
        primary_masks = dm.fault_masks(cfg, (S, K, N), tag)
    spare_masks = dm.fault_masks(cfg, (S, K, B), tag, stage=dm.STAGE_SPARE_FAULTS)

    cell_max = float((1 << spec.cell_bits) - 1)
    t_u = _unit_view(target, spec.rows)  # (S, R, rows, N)
    units0 = _unit_fault_error(
        t_u,
        (_unit_view(primary_masks[0], spec.rows), _unit_view(primary_masks[1], spec.rows)),
        spec,
    )  # (S, R, N)
    on_sp = _unit_view(spare_masks[0].astype(jnp.float32), spec.rows)  # (S,R,rows,B)
    off_sp = _unit_view(spare_masks[1].astype(jnp.float32), spec.rows)

    sal0 = column_salience(target, primary_masks, spec)  # (N,)
    # every column group at once: columns padded to n_groups * cols (padded
    # columns have zero salience, so no spare ever moves one), spares
    # split B -> (group, B_per); one vmapped greedy per (s, r, group) unit
    C = spec.cols
    pad = n_groups * C - N
    t_g = jnp.pad(t_u, ((0, 0), (0, 0), (0, 0), (0, pad))).reshape(S, R, spec.rows, n_groups, C)
    on_g = on_sp.reshape(S, R, spec.rows, n_groups, B_per)
    off_g = off_sp.reshape(S, R, spec.rows, n_groups, B_per)
    # err_sp[s, r, g, b, v]: fault error of group g's spare b's (s, r) unit
    # holding logical column v's targets for that unit
    err_sp = jnp.einsum("srkgb,srkgv->srgbv", on_g, cell_max - t_g) + jnp.einsum(
        "srkgb,srkgv->srgbv", off_g, t_g
    )
    units_g = jnp.pad(units0, ((0, 0), (0, 0), (0, pad))).reshape(S * R * n_groups, C)
    sal_u, victim_u, gather_u = jax.vmap(_greedy_assign)(
        units_g, err_sp.reshape(S * R * n_groups, B_per, C)
    )
    col0 = (jnp.arange(n_groups, dtype=jnp.int32) * C)[:, None]  # group's first column
    spare0 = (jnp.arange(n_groups, dtype=jnp.int32) * B_per)[:, None]  # its first spare
    victim = jnp.where(
        victim_u.reshape(S, R, n_groups, B_per) >= 0,
        victim_u.reshape(S, R, n_groups, B_per) + col0, -1,
    ).reshape(S, R, B)
    gather_u = gather_u.reshape(S, R, n_groups, C)
    gather = jnp.where(gather_u >= C, gather_u - C + N + spare0, gather_u + col0)
    gather = gather.reshape(S, R, n_groups * C)[:, :, :N]
    units = sal_u.reshape(S, R, n_groups * C)[:, :, :N]

    # Program the chosen targets into the spare block through the standard
    # write-verify pipeline (independent "spare_program" pulse keys), then
    # read back through drift/IR drop at each group's true wordline
    # position: a spare physically sits right past its own group's data
    # columns (group-local mux), never at the near-driver corner — so
    # repair is not optimistically biased under r_line_ohm.  Each spare
    # column's (s, r) unit holds its own victim's targets — per-array
    # muxing means one physical spare column serves up to S x R victims.
    vt = jnp.take_along_axis(
        t_u, jnp.clip(victim, 0, N - 1)[:, :, None, :], axis=3
    )  # (S, R, rows, B)
    vt = jnp.where((victim >= 0)[:, :, None, :], vt, 0.0)
    spare_target = vt.reshape(S, R * spec.rows, B)[:, :K, :]
    key = dm._stage_key(cfg, dm.STAGE_SPARE_PROGRAM, tag)
    g = dm.write_verify_fixed(spare_target, spare_masks, key, spec, cfg)
    # spare b of group gi sits at wordline position min((gi+1)*cols, N) + (b - gi*B_per)
    group_end = np.minimum((np.arange(n_groups) + 1) * C, N)
    offsets = np.repeat(group_end - np.arange(n_groups) * B_per, B_per)
    g_spare = dm.read_effective_codes(g, spec, cfg, col_offset=offsets)

    w = _slice_weights(spec)
    return RepairPlan(
        victim=victim,
        out_gather=gather,
        g_spare=g_spare,
        salience_before=sal0,
        salience_after=jnp.sum(units * w[:, None, None], axis=(0, 1)),
        rows=int(spec.rows),
    )


def apply_repair(g_eff_primary: jnp.ndarray, plan: Optional[RepairPlan]) -> jnp.ndarray:
    """Scatter programmed spare cells into victim positions: the repaired
    (S, K, N) layout every kernel consumes with zero steady-state overhead.

    Column-separability *per physical crossbar* makes this exactly
    equivalent to running the physical (S, K, N + B) layout and gathering
    each (slice, row group) unit's partial outputs through its
    ``plan.out_gather`` table before the digital shift-and-add / row-group
    merge — see tests/test_repair.py, which pins the equivalence down
    bit-for-bit.
    """
    if plan is None:
        return g_eff_primary
    S, K, N = g_eff_primary.shape
    R = plan.out_gather.shape[1]
    g_full = jnp.concatenate([g_eff_primary, plan.g_spare], axis=2)
    rg = jnp.minimum(jnp.arange(K) // plan.rows, R - 1)
    idx = plan.out_gather[:, rg, :]  # (S, K, N): per-row-of-cells gather
    return jnp.take_along_axis(g_full, idx, axis=2)


def repaired_effective_cells(
    w_codes_biased: jnp.ndarray,
    spec: CrossbarSpec,
    cfg: dm.DeviceConfig,
    *,
    with_report: bool = False,
) -> Tuple[jnp.ndarray, Optional[RepairPlan], Optional[Any]]:
    """Program + repair in one pass: (repaired g_eff, plan, report).

    Equivalent to ``effective_cell_codes(wb, spec, cfg)`` but also returns
    the plan (spare block, gather table, saliences) for callers — notably
    ``programmed.program_layer`` — that record the repair; the programming
    intermediates are shared with the planner, never recomputed.

    This is the **single derivation site** for the programming
    intermediates.  ``with_report=True`` swaps the trace-safe fixed-
    iteration pulse loop for ``program.write_verify`` — identical stage
    keys, so the cells are bit-identical (pinned by
    ``test_programming_is_deterministic``) — and returns its convergence
    ``ProgramReport`` as the third element (None otherwise).
    """
    if with_report:
        from repro.device.program import write_verify

        target = dm.target_cell_codes(w_codes_biased, spec)
        tag = dm._slab_tag(w_codes_biased)
        masks = dm.fault_masks(cfg, target.shape, tag)
        g, report = write_verify(
            w_codes_biased, spec, cfg, target=target, tag=tag, masks=masks
        )
        g_eff = dm.read_effective_codes(g, spec, cfg)
    else:
        g_eff, target, tag, masks = dm._programmed_effective(
            w_codes_biased, spec, cfg
        )
        report = None
    rplan = plan_repair(
        w_codes_biased, spec, cfg, target=target, tag=tag, primary_masks=masks
    )
    return apply_repair(g_eff, rplan), rplan, report


def repair_report(plan: Optional[RepairPlan]) -> Optional[RepairReport]:
    """Materialize the host-side summary (programming time only, not under
    trace — the plan's arrays are concretized)."""
    if plan is None:
        return None
    victim = np.asarray(plan.victim)
    return RepairReport(
        budget=int(victim.size),
        n_repaired=int((victim >= 0).sum()),
        repaired_cols=tuple(sorted({int(v) for v in victim.ravel() if v >= 0})),
        salience_before=float(np.asarray(plan.salience_before).sum()),
        salience_after=float(np.asarray(plan.salience_after).sum()),
    )
