"""Batched serving, split into a model runner and a slot scheduler.

Two layers (the scheduler/model-runner split):

``ModelRunner`` owns the *model* half of serving: the params, the
programmed crossbar chip and its whole lifecycle (program-once
compilation, artifact store save/restore, aging, health probes,
compensation, zero-downtime hot-swap/refresh), the jitted prefill/decode
step functions, and sampling.  It is stateless with respect to traffic —
it does not know about slots, requests or queues — so any number of
scheduling policies can drive one runner.

``ServingEngine`` is the synchronous slot scheduler on top (vLLM-lite,
adapted to JAX static shapes):
  * a fixed pool of ``max_batch`` cache slots, each holding one request's
    KV/state cache at its own position;
  * admission: a pending request is prefilled with a batch-1 prefill
    (prompt padded to a bucket to bound recompilation) and its cache is
    scattered into the slot pool;
  * decode: one jitted ``decode_step`` advances *all* occupied slots each
    tick with per-slot positions; finished slots are freed and refilled
    without stalling the others.

The continuous-batching traffic tier builds on the same runner:
``serving.scheduler.ContinuousBatchingScheduler`` adds per-request
deadlines, mid-flight eviction and a block-allocated KV cache
(``serving.kvcache``), and ``serving.farm.ChipFarm`` routes requests
across N programmed replicas restored from one artifact store.

Sampling is greedy or temperature-based with a per-runner PRNG; generation
is deterministic given (seed, admission order), which the tests assert.

Crossbar serving: pass ``crossbar=CrossbarMode(enabled=True, device=...)``
and the runner compiles every projection onto programmed crossbars **once**
at construction (``repro.device.programmed.program_model``) — the paper's
program-once premise as a serving feature.  Every prefill/decode then runs
the steady-state artifact path inside the jitted step functions: one fixed
noisy chip across the whole engine lifetime, no per-call reprogramming.
Artifacts are name-keyed, so MoE expert banks and tied LM heads serve from
the crossbar too (the tied head from a transpose programmed once at
construction).  ``spare_cols=`` exposes the fault-aware spare-column repair
budget (``device.repair``) at deploy time; ``repair_reports()`` summarizes
what the planner remapped.

Persistence: ``save_artifacts(dir)`` writes the programmed chip —
effective cells, frozen scales, write-verify reports, spare blocks and
gather tables — through ``repro.checkpoint``; a later
``ServingEngine(..., restore_artifacts=dir)`` restores the *same* chip
bit-for-bit and skips reprogramming entirely (restart latency is file I/O,
not write-verify).  Both construction-time restore *and* ``hot_swap()``
run the same ``analysis.verify_store`` fail-fast static verification
before binding, so a corrupt store is refused up front instead of hitting
mid-flight serving.

Mesh serving: pass ``mesh=`` (plus ``param_axes=`` from ``init_model``)
and every jitted step runs under the mesh with the config's layout
overrides, so the model's ``shard_map`` EP/TP paths engage; programmed
artifacts are sharded with the same PartitionSpecs as the weights they
shadow (``device.programmed.shard_artifacts``) and the bodies rebind
rank-local slices by name — expert-parallel serving is bit-identical to
the single-device chip (tests/test_sharded_artifacts.py).  Saved stores
record the deployment sharding; restore re-places shards on the mesh.
``verify_coverage`` (default on) runs the structural name-set check at
construction: one abstract trace asserts the forward consumes exactly the
emitted artifact name set, failing loudly on drift a miss counter cannot
see (an orphaned artifact misses nothing — nothing ever looks it up).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.models.layers import CrossbarMode, collect_counts, crossbar_mode
from repro.serving import tracing


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32 tokens (or (S, D) embeddings)
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # allow silently truncating a prompt longer than max_seq to its last
    # max_seq tokens-worth prefix; without it an over-length prompt is
    # refused at submit() with a ValueError
    truncate: bool = False
    # traffic tier (serving.scheduler): absolute tick by which the request
    # must finish, else it is evicted with expired=True; None = no deadline
    deadline: Optional[int] = None
    # streaming: called as on_token(req, tok) for every generated token,
    # including the prefill-sampled first token of recurrent archs
    on_token: Optional[Callable[["Request", int], None]] = None
    arrival: int = 0  # scheduler tick at submit time
    finish: Optional[int] = None  # scheduler tick after the finishing step
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    expired: bool = False


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


def _row_sums(counts: Dict[str, np.ndarray], rows) -> Dict[str, int]:
    """A step's counts as host ints: a count kept per token summed over the
    token rows ``rows`` selects (an index into the flattened tokens; None
    takes them all), a scalar count as it is."""
    out = {}
    for k, v in counts.items():
        v = np.asarray(v)
        if v.ndim and rows is not None:
            v = v.reshape(-1)[rows]
        out[k] = int(np.sum(v))
    return out


class ModelRunner:
    """The model half of serving: chip + jitted steps + sampling.

    Owns everything about *how* one token batch is computed — programmed
    crossbar artifacts and their lifecycle, mesh placement, the jitted
    prefill/decode closures, the sampling PRNG — and nothing about *which*
    requests run when.  Schedulers (the slot loop in ``ServingEngine``,
    the continuous-batching tier in ``serving.scheduler``) hold the
    traffic state and call into one runner.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        max_seq: int = 512,
        temperature: float = 0.0,
        seed: int = 0,
        crossbar: Optional[CrossbarMode] = None,
        spare_cols: Optional[int] = None,
        restore_artifacts: Optional[str] = None,
        mesh=None,
        param_axes=None,
        verify_coverage: bool = True,
        expert_chips=None,
        plan=None,
    ):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        # mesh serving: every jitted step runs under ``use_mesh(mesh,
        # layout_overrides(cfg))`` so the model's shard_map EP/TP paths
        # engage; ``param_axes`` (the logical-axes tree from init_model)
        # lets the runner shard programmed artifacts with the same specs as
        # the weights they shadow (device.programmed.shard_artifacts)
        self.mesh = mesh
        self.param_axes = param_axes
        # fleet realism: one DeviceConfig.chip identity per expert, so the
        # slabs an EP mesh places on different ranks draw decorrelated
        # device perturbations (device.programmed.program_layer(chips=));
        # remembered so refresh() reprograms the same fleet
        self.expert_chips = tuple(expert_chips) if expert_chips is not None else None
        # chip-plan compiler (core.planner.ChipPlan): per-layer heterogeneous
        # datapath / ADC schedule / spare budget, threaded into program_model
        # at deploy time and again on refresh() — the reprogrammed fleet must
        # be the chip the plan admitted
        self.plan = plan
        self.crossbar = self._program_crossbars(crossbar, spare_cols, restore_artifacts)
        if verify_coverage:
            self.verify_crossbar_coverage()
        # the jitted steps take the programmed chip (``self.artifacts``) as
        # their first argument, never as a trace-time constant: the
        # executables hold no copy of its arrays, and a swapped chip reuses
        # them while its static aux is unchanged.  The decode step donates
        # its cache (``fn(artifacts, params, tokens, pos, cache)``): the step
        # writes each token into it in place, and every caller replaces its
        # cache with the one returned
        self.decode_fn = jax.jit(self._on_chip(model_lib.decode_step), donate_argnums=4)
        self.prefill_fn = jax.jit(self._on_chip(model_lib.prefill))
        # counts of admissions not yet read, and the slots that carry a
        # request in the next decode, which its scheduler sets (see
        # ``decode``)
        self._admit_counts: List[Tuple[Dict, slice]] = []
        self.active_slots: Optional[np.ndarray] = None

    # ------------------------------------------------------------------
    @property
    def _tie_lm_head(self) -> bool:
        return self.cfg.tie_embeddings and self.cfg.frontend == "token"

    def _verify_store(self, directory: str, slot: Optional[str], what: str):
        """Fail-fast static store verification shared by construction-time
        restore and ``hot_swap`` — same rules, same orphaned-leaf carve-out.

        Verifies from manifests alone, before any array loads or binding: a
        corrupt slot pointer, undecodable spec/plan, inconsistent leaf
        shapes or a wrong name-set is refused with the failing rule named,
        instead of surfacing as a silent per-call reprogramming fallback
        mid-serving.  Returns the expected name -> shape map for the
        follow-up binding cross-check.
        """
        from repro.analysis.store import verify_store
        from repro.device.programmed import expected_artifact_names

        expected = expected_artifact_names(self.params, tie_lm_head=self._tie_lm_head)
        vreport = verify_store(directory, expected=expected, slot=slot)
        # orphaned leaves (store ⊃ model) are left to verify_coverage: a
        # superset store serves correctly, and that check has an explicit
        # opt-out (verify_coverage=False) for exotic setups
        fatal = [
            f for f in vreport.findings
            if not (f.rule == "name-set" and "orphaned leaf" in f.message)
        ]
        if fatal:
            vreport.findings[:] = fatal
            raise ValueError(
                f"{what} store failed static verification "
                "(repro.analysis.verify_store): it is internally "
                "inconsistent or does not match this model —\n"
                + vreport.summary()
            )
        return expected

    def _program_crossbars(
        self,
        crossbar: Optional[CrossbarMode],
        spare_cols: Optional[int] = None,
        restore_artifacts: Optional[str] = None,
    ):
        """Program-once compilation of the model's weights (deploy time).

        When crossbar serving is requested without prebuilt artifacts, walk
        the params and compile every projection now — every subsequent
        prefill/decode is pure steady-state (and under a noisy
        ``DeviceConfig`` the whole engine serves from one fixed chip
        instead of redrawing noise per layer call).

        ``spare_cols`` (constructor arg) overrides the device's
        spare-column repair budget at deploy time: the fault-aware planner
        (``device.repair``) then remaps the worst stuck-cell columns of
        every projection into programmed spares before serving begins.

        ``restore_artifacts`` restores a previously ``save_artifacts``-ed
        programmed chip instead of reprogramming: the name-keyed artifact
        store is loaded bit-for-bit (fault fields, write-verify reports,
        repair tables included) and no ``program_layer`` call runs.
        """
        if restore_artifacts is not None:
            if crossbar is None or not crossbar.enabled:
                raise ValueError(
                    "restore_artifacts= needs crossbar serving enabled "
                    "(pass crossbar=CrossbarMode(enabled=True, ...))"
                )
            if crossbar.programmed is not None:
                raise ValueError(
                    "restore_artifacts= with prebuilt CrossbarMode.programmed "
                    "artifacts: pick one source of truth"
                )
            if spare_cols is not None:
                # 0 included: an explicit disable can no more be applied to
                # a baked chip than a new budget can — silently serving the
                # repaired artifacts would ignore the operator's override
                raise ValueError(
                    "spare_cols= cannot rebudget a restored chip (not even "
                    "to 0): the repair plan was baked in when the artifacts "
                    "were programmed — reprogram with the desired budget"
                )
            if self.plan is not None:
                # same bakery rule: a restored chip was compiled under the
                # plan recorded in its artifacts (each carries its
                # LayerPlan); a different plan needs a reprogram
                raise ValueError(
                    "plan= cannot replan a restored chip: the datapath / ADC "
                    "/ spare choices were baked in when the artifacts were "
                    "programmed — reprogram with the desired plan"
                )
            from repro.checkpoint import restore_programmed

            expected = self._verify_store(restore_artifacts, None, "restore_artifacts=")
            # restore re-places shards on the engine's mesh from the specs
            # recorded at save time; _shard_artifacts below re-derives from
            # param_axes as well, so either source of truth suffices
            prog = restore_programmed(restore_artifacts, mesh=self.mesh)
            # a stale or mismatched store would resolve no artifacts and
            # silently degrade every projection to per-call reprogramming —
            # the exact silent fallback this engine exists to prevent, so
            # cross-check the store against what this model would program
            bad = sorted(
                name for name, shape in expected.items()
                if prog.lookup(name, shape) is None
            )
            if bad:
                raise ValueError(
                    f"restored artifact store at {restore_artifacts!r} does not "
                    f"match this model: {len(bad)}/{len(expected)} projections "
                    f"missing or shape-mismatched ({', '.join(bad[:5])}"
                    + (", ..." if len(bad) > 5 else "")
                    + ") — was it saved from a different model/config?"
                )
            return dataclasses.replace(crossbar, programmed=self._shard_artifacts(prog))
        # spare_cols=0 means "no repair" and is a no-op wherever repair could
        # not happen anyway; a *positive* budget that cannot take effect is a
        # misconfiguration — silently serving unrepaired while the operator
        # believes a repair budget is active would be worse than failing
        if crossbar is None or not crossbar.enabled or crossbar.programmed is not None:
            if spare_cols:
                raise ValueError(
                    "spare_cols= needs crossbar serving with a DeviceConfig "
                    "to repair and no prebuilt artifacts (set spare_cols on "
                    "the DeviceConfig passed to program_model instead)"
                )
            return crossbar
        device = crossbar.device
        if spare_cols is not None:
            if device is None:
                if spare_cols:
                    raise ValueError(
                        "spare_cols= without a CrossbarMode.device: there is "
                        "no fault model to repair against"
                    )
            else:
                device = device.replace(spare_cols=spare_cols)
                from repro.device import wants_repair

                if spare_cols > 0 and not wants_repair(device):
                    raise ValueError(
                        f"spare_cols={spare_cols} on a device with no "
                        "stuck-at faults (p_stuck_on == p_stuck_off == 0): "
                        "nothing to repair"
                    )
                crossbar = dataclasses.replace(crossbar, device=device)
        from repro.core.crossbar import DEFAULT_SPEC
        from repro.device.programmed import program_model

        prog = program_model(
            self.params,
            spec=DEFAULT_SPEC.replace(row_ranges=self.cfg.crossbar_row_ranges),
            device=device,
            fast=crossbar.fast,
            # tied LM heads serve from a transpose programmed once, bound to
            # the embedding's name (name-keyed binding makes this possible)
            tie_lm_head=self._tie_lm_head,
            expert_chips=self.expert_chips,
            plan=self.plan,
        )
        return dataclasses.replace(crossbar, programmed=self._shard_artifacts(prog))

    def _shard_artifacts(self, prog):
        """Place every artifact on the runner's mesh with its weight's spec.

        No-op without a mesh or without ``param_axes`` (artifacts stay
        replicated — the shard_map bodies still slice them per rank on the
        fly, so correctness never depends on placement, only memory/traffic
        does: an unplaced 8-plane ``g_eff`` would otherwise be resident on
        every device).
        """
        if self.mesh is None or self.param_axes is None or prog is None:
            return prog
        from jax.sharding import PartitionSpec as P

        from repro.device.programmed import join_path, shard_artifacts
        from repro.models.layers import layout_overrides, pspec, use_mesh

        flat_axes = jax.tree_util.tree_flatten_with_path(
            self.param_axes, is_leaf=lambda x: isinstance(x, tuple)
        )[0]
        axes_by_name = {join_path(p): a for p, a in flat_axes}
        shapes_by_name = {
            join_path(p): tuple(leaf.shape)
            for p, leaf in jax.tree_util.tree_flatten_with_path(self.params)[0]
        }
        specs = {}
        with use_mesh(self.mesh, layout_overrides(self.cfg)):
            for name, art in prog.by_name.items():
                axes = axes_by_name.get(name)
                if axes is None:
                    continue
                spec = pspec(axes, self.mesh)
                wshape = shapes_by_name.get(name)
                if art.shape == wshape:
                    specs[name] = spec
                elif wshape is not None and art.shape == tuple(reversed(wshape)):
                    # the tied-head artifact is the embedding's transpose,
                    # programmed under the embedding's name: reverse the spec
                    specs[name] = P(*reversed(tuple(spec) + (None,) * (len(wshape) - len(tuple(spec)))))
        return shard_artifacts(prog, self.mesh, specs)

    def verify_crossbar_coverage(self) -> None:
        """Structural name-set check at construction (abstract trace only).

        Traces one forward with ``jax.eval_shape`` under the runner's
        crossbar mode and asserts the programmed model's emitted name set
        was consumed exactly — a renamed layer or an artifact no call site
        serves fails construction loudly, *before* the first request
        (and before the miss counter could ever catch the orphaned-artifact
        direction, which produces zero misses).  No kernels execute and
        nothing is allocated.
        """
        if self.crossbar is None or self.crossbar.programmed is None:
            return
        from repro.device import programmed as prog_mod
        from repro.models import layers as layers_mod

        if self.cfg.frontend == "token":
            inp = jax.ShapeDtypeStruct((1, 4), jnp.int32)
        else:
            inp = jax.ShapeDtypeStruct((1, 4, self.cfg.d_model), jnp.float32)
        # snapshot the ambient trace-time records: this internal trace must
        # neither clobber a caller's in-flight consumption record nor leave
        # its own misses behind for an operator to misread as serving-time
        before_consumed = prog_mod.consumed_artifact_names()
        before_misses = layers_mod.crossbar_miss_counts()
        prog_mod.reset_consumed_artifact_names()
        try:
            jax.eval_shape(
                lambda p, t: self._with_crossbar(
                    lambda: model_lib.forward(p, self.cfg, t)
                ),
                self.params,
                inp,
            )
            self.crossbar.programmed.verify_consumed()
        finally:
            prog_mod.reset_consumed_artifact_names()
            for n in before_consumed:
                prog_mod.record_artifact_consumed(n)
            layers_mod.restore_crossbar_misses(before_misses)

    def save_artifacts(self, directory: str, slot: Optional[str] = None) -> str:
        """Persist the programmed chip so a restart can restore instead of
        reprogram (``ServingEngine(..., restore_artifacts=directory)``).
        ``slot`` writes into the double-buffered A/B layout (see
        ``checkpoint.save_programmed``; commit with ``swap_active``)."""
        if self.crossbar is None or self.crossbar.programmed is None:
            raise ValueError(
                "no programmed artifacts to save: construct the engine with "
                "crossbar=CrossbarMode(enabled=True, ...) first"
            )
        from repro.checkpoint import save_programmed

        return save_programmed(directory, self.crossbar.programmed, slot=slot)

    def repair_reports(self):
        """Path -> spare-column ``RepairReport`` for every repaired
        projection of the programmed model ({} when repair is off)."""
        if self.crossbar is None or self.crossbar.programmed is None:
            return {}
        return self.crossbar.programmed.repair_reports()

    # ------------------------------------------------------------------
    # Chip lifecycle: monitor -> compensate -> refresh
    # ------------------------------------------------------------------

    @property
    def programmed(self):
        """The bound ``ProgrammedModel`` (None when not crossbar-serving)."""
        if self.crossbar is None:
            return None
        return self.crossbar.programmed

    @property
    def uptime_s(self) -> float:
        """Fleet service time of the bound chips, seconds since programming."""
        prog = self.programmed
        return prog.t_service_s if prog is not None else 0.0

    def _require_programmed(self, what: str):
        prog = self.programmed
        if prog is None:
            raise ValueError(
                f"{what} needs programmed crossbar serving: construct the "
                "engine with crossbar=CrossbarMode(enabled=True, ...)"
            )
        return prog

    def _rebind(self, prog) -> None:
        """Swap the served chip.

        The jitted prefill and decode steps take the artifact arrays as an
        argument, so the next call serves the new chip; it recompiles only
        where the artifacts' static aux changed (``age`` advances
        ``t_service_s``, which is aux).  KV caches, slot state and pending
        requests live in the scheduler layer and are untouched, so
        in-flight requests continue on the new chip at the next tick — the
        zero-downtime part of ``hot_swap``.
        """
        self.crossbar = dataclasses.replace(self.crossbar, programmed=prog)

    def age(self, dt_s: float) -> None:
        """Advance every bound chip ``dt_s`` seconds of service.

        The lifecycle clock: cells decay through the device's retention
        power law (``device.programmed.age_artifact``) without
        reprogramming.  Drift-free configs only advance the clock
        (bit-identical serving).
        """
        prog = self._require_programmed("age()")
        self._rebind(prog.age(dt_s))

    def health_check(self, n_probes: Optional[int] = None, seed: int = 0,
                     budget: Optional[float] = None):
        """Probe every bound artifact against its frozen digital reference.

        Returns a ``device.health.HealthReport``; ``report.flagged`` names
        the layers whose drift error crossed the budget — the refresh
        candidates.  Purely digital, does not perturb the chips.
        """
        from repro.device import health as health_mod

        prog = self._require_programmed("health_check()")
        kw = {}
        if n_probes is not None:
            kw["n_probes"] = n_probes
        if budget is not None:
            kw["budget"] = budget
        return health_mod.health_check(prog, seed=seed, **kw)

    def compensate(self, n_probes: Optional[int] = None, seed: int = 0) -> None:
        """Refit the free digital drift compensation on every noisy chip.

        Updates each artifact's ``comp_scale`` (closed-form power-law
        rescale + probe-fit residual, ``device.health.fit_compensation``)
        and rebinds — zero reprogramming, recovers most of the drift-accrued
        logit error between refreshes.
        """
        from repro.device import health as health_mod

        prog = self._require_programmed("compensate()")
        kw = {"n_probes": n_probes} if n_probes is not None else {}
        self._rebind(health_mod.compensate_model(prog, seed=seed, **kw))

    def hot_swap(self, directory: str, slot: Optional[str] = None) -> None:
        """Rebind the chip from an artifact store without stopping serving.

        Runs the *same* ``analysis.verify_store`` fail-fast static
        verification as construction-time ``restore_artifacts=`` (same
        orphaned-leaf carve-out), restores ``directory`` (following the
        ``ACTIVE`` slot pointer unless ``slot`` is forced), cross-checks it
        against this model's expected projection set, re-places it on the
        runner's mesh, and swaps between decode steps — in-flight requests
        keep their caches and continue on the refreshed chip at the next
        tick.  A corrupt or mismatched store is refused up front and the
        old chip keeps serving.  A swap onto a just-reprogrammed store is
        bit-identical to an engine freshly constructed on that chip
        (programming is deterministic; the store round-trips exact dtypes).
        """
        self._require_programmed("hot_swap()")
        from repro.checkpoint import restore_programmed

        expected = self._verify_store(directory, slot, "hot_swap")
        prog = restore_programmed(directory, mesh=self.mesh, slot=slot)
        bad = sorted(
            name for name, shape in expected.items()
            if prog.lookup(name, shape) is None
        )
        if bad:
            raise ValueError(
                f"hot_swap store at {directory!r} does not match this model: "
                f"{len(bad)}/{len(expected)} projections missing or "
                f"shape-mismatched ({', '.join(bad[:5])}"
                + (", ..." if len(bad) > 5 else "") + ")"
            )
        self._rebind(self._shard_artifacts(prog))

    def refresh(self, directory: Optional[str] = None) -> Optional[str]:
        """Reprogram fresh chips and swap them in — the lifecycle reset.

        Reprograms every projection from the runner's params under the
        construction-time device config (deterministic: the same chip the
        engine started with, at service time zero).  With ``directory``,
        the fresh chips are written into the *inactive* store slot while
        the old ones keep serving, the ``ACTIVE`` pointer is atomically
        swapped, and the runner hot-swaps from the store (serving exactly
        what a restart would restore); returns the committed slot.  Without
        a directory the fresh chips are rebound directly.
        """
        self._require_programmed("refresh()")
        from repro.device.programmed import program_model

        prog = program_model(
            self.params,
            device=self.crossbar.device,
            fast=self.crossbar.fast,
            tie_lm_head=self._tie_lm_head,
            expert_chips=self.expert_chips,
            plan=self.plan,
        )
        if directory is None:
            self._rebind(self._shard_artifacts(prog))
            return None
        from repro.checkpoint import active_slot, save_programmed, swap_active

        target = "B" if active_slot(directory) == "A" else "A"
        save_programmed(directory, prog, slot=target)
        swap_active(directory, target)
        self.hot_swap(directory)
        return target

    def _with_crossbar(self, fn, artifacts=None):
        """Run ``fn`` under the runner's mesh and crossbar mode, with the
        programmed model's name-keyed artifact table bound for the dynamic
        scope (works at jit trace time — lookups resolve by name, not by
        leaf identity, so any congruent params tree serves).  ``artifacts``
        (a ``ProgrammedModel.artifacts`` tree, traced inside a jitted step)
        stands in for the bound chip's arrays.  With a mesh, the model's
        shard_map EP/TP paths engage and their bodies rebind rank-local
        artifact slices."""
        with contextlib.ExitStack() as stack:
            if self.mesh is not None:
                from repro.models.layers import layout_overrides, use_mesh

                stack.enter_context(use_mesh(self.mesh, layout_overrides(self.cfg)))
                stack.enter_context(self.mesh)
            mode = self.crossbar
            if mode is not None:
                if artifacts is not None:
                    from repro.device.programmed import ProgrammedModel

                    mode = dataclasses.replace(mode, programmed=ProgrammedModel(artifacts))
                stack.enter_context(crossbar_mode(mode))
                if mode.programmed is not None:
                    stack.enter_context(mode.programmed.bind())
            return fn()

    def _on_chip(self, step):
        """``step(params, cfg, *args)`` as ``fn(artifacts, params, *args)``,
        run under ``_with_crossbar`` with the traced artifacts bound, which
        returns the step's outputs and then the counts the model recorded
        (``models.layers.record_count``: ``{}`` for a model without experts).
        The wrapper keeps the step's name, which compile logs and profiles
        show."""
        def fn(artifacts, params, *args):
            def counted():
                with collect_counts() as counts:
                    out = step(params, self.cfg, *args)
                return (*out, counts)

            return self._with_crossbar(counted, artifacts)

        fn.__name__ = fn.__qualname__ = step.__name__
        return fn

    @property
    def artifacts(self):
        """The bound chip's artifact tree (``ProgrammedModel.artifacts``),
        the first argument of ``decode_fn`` and ``prefill_fn``."""
        prog = self.programmed
        return None if prog is None else prog.artifacts

    # ------------------------------------------------------------------
    # Scheduler-facing surface: cache init, prefill-admit, decode, sample
    # ------------------------------------------------------------------

    def init_cache(self, batch: int, dtype=jnp.float32):
        """A dense slot-pool cache sized to this runner's ``max_seq``."""
        return model_lib.init_cache(self.cfg, batch, self.max_seq, dtype=dtype)

    def check_prompt(self, prompt, truncate: bool) -> int:
        """Validate a prompt against ``max_seq``; returns the effective
        (possibly truncated) prefill length.

        A prompt longer than ``max_seq`` cannot be coherently prefilled —
        the slot pool has no room for its tail — so it is refused with a
        clear error unless the caller explicitly opted into truncation
        (``truncate=True`` keeps the first ``max_seq`` tokens and admits
        with pos/last_tok derived from the truncated length).
        """
        S = len(prompt)
        if S > self.max_seq:
            if not truncate:
                raise ValueError(
                    f"prompt of length {S} exceeds max_seq={self.max_seq}: "
                    "it cannot be prefilled into the slot pool — raise "
                    "max_seq, shorten the prompt, or pass truncate=True to "
                    "serve the first max_seq tokens"
                )
            return self.max_seq
        return S

    def admit_slot(self, cache, slot: int, req: Request):
        """Prefill one request and scatter its cache into slot ``slot``.

        Returns ``(cache, pos, last_tok, first_tok)`` where ``first_tok``
        is the prefill-sampled first generated token for recurrent archs
        (None for attention, which re-issues the last prompt token on the
        first decode tick instead).
        """
        S = self.check_prompt(req.prompt, req.truncate)
        # Recurrent archs (ssm/hybrid) must not process padding tokens —
        # their state would absorb them — so they prefill exact lengths;
        # attention caches tolerate padding (masked by position), so they
        # use buckets + an idempotent catch-up re-issue of token S-1.
        recurrent = self.cfg.family in ("ssm", "hybrid")
        bucket = S if recurrent else min(_bucket(S), self.max_seq)
        with tracing.span("runner.admit", rid=req.rid, slot=slot, prompt=S, bucket=bucket):
            prompt = np.zeros((1, bucket), np.int32)
            # S <= bucket always (check_prompt clamps S to max_seq >= bucket),
            # so the copy below never silently drops tokens the bookkeeping
            # would then point past
            prompt[0, :S] = req.prompt[:S]
            logits, filled, counts = self.prefill(jnp.asarray(prompt), self.init_cache(1))
            if counts:
                # read with the next decode step's logits: no host sync here;
                # the prompt's rows are the first S of the bucket
                self._admit_counts.append((counts, slice(0, S)))
            cache = jax.tree.map(
                lambda big, one: big.at[:, slot].set(one[:, 0]), cache, filled
            )
            if recurrent:
                tok = int(self.sample(np.asarray(logits, np.float32))[0])
                return cache, S, tok, tok
        # pos/last_tok from the *effective* length: after truncation both
        # point at the last token that was actually prefilled
        return cache, S - 1, int(np.asarray(req.prompt)[S - 1]), None

    def prefill(self, tokens, cache):
        """One jitted prefill of ``tokens`` (B, S) into ``cache``; returns
        ``(last_logits, cache, counts)`` as device arrays."""
        return self.prefill_fn(self.artifacts, self.params, tokens, cache)

    def decode(self, last_tok: np.ndarray, pos: np.ndarray, cache):
        """One jitted decode tick over the whole slot pool; returns
        ``(logits, cache)`` with logits as host float32.  ``cache`` is
        donated: use the returned one.  The launch
        returns before the device finishes; the fetch waits for it.  The
        step's counts, and those of the admissions since the last fetch
        (``admit_<name>``, summed), come to the host with the logits and
        go on the fetch span; a count kept per token is summed over the
        slots ``active_slots`` marks (all of them where it is None) and
        over each admission's prompt."""
        with tracing.span("runner.launch"):
            toks = jnp.asarray(np.asarray(last_tok)[:, None])
            logits, cache, counts = self.decode_fn(
                self.artifacts, self.params, toks, jnp.asarray(pos), cache
            )
        with tracing.span("runner.fetch") as sp:
            logits, counts, admitted = jax.device_get(
                (logits, counts, [c for c, _ in self._admit_counts]))
            logits = np.asarray(logits, np.float32)
            meta = _row_sums(counts, self.active_slots)
            for c, (_, rows) in zip(admitted, self._admit_counts):
                for k, v in _row_sums(c, rows).items():
                    meta[f"admit_{k}"] = meta.get(f"admit_{k}", 0) + v
            self._admit_counts = []
            if meta:
                sp.set_metadata(**meta)
        return logits, cache

    def sample(self, logits: np.ndarray) -> np.ndarray:
        with tracing.span("runner.sample"):
            if self.temperature <= 0.0:
                return np.argmax(logits, axis=-1).astype(np.int32)
            self.key, sub = jax.random.split(self.key)
            g = jax.random.gumbel(sub, logits.shape)
            return np.asarray(
                jnp.argmax(logits / self.temperature + g, axis=-1), np.int32
            )


class ServingEngine:
    """Slot scheduler over a ``ModelRunner`` (the pre-traffic-tier loop).

    Composes a runner with a fixed slot pool and a FIFO pending queue;
    ``step()`` admits and advances, ``run_until_done()`` drains.  All
    model/chip concerns (programming, lifecycle, persistence, sampling)
    delegate to the runner — ``eng.crossbar``, ``eng.hot_swap(...)`` etc.
    keep working as before the scheduler/model-runner split.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        max_batch: int = 4,
        max_seq: int = 512,
        temperature: float = 0.0,
        seed: int = 0,
        crossbar: Optional[CrossbarMode] = None,
        spare_cols: Optional[int] = None,
        restore_artifacts: Optional[str] = None,
        mesh=None,
        param_axes=None,
        verify_coverage: bool = True,
        expert_chips=None,
        plan=None,
        rid_start: int = 0,
    ):
        self.runner = ModelRunner(
            cfg,
            params,
            max_seq=max_seq,
            temperature=temperature,
            seed=seed,
            crossbar=crossbar,
            spare_cols=spare_cols,
            restore_artifacts=restore_artifacts,
            mesh=mesh,
            param_axes=param_axes,
            verify_coverage=verify_coverage,
            expert_chips=expert_chips,
            plan=plan,
        )
        self.max_batch = max_batch
        self.cache = self.runner.init_cache(max_batch)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.pos = np.zeros(max_batch, np.int32)  # position of next write
        self.last_tok = np.zeros(max_batch, np.int32)
        self.pending: List[Request] = []
        # completion ledger: step() records every finished request here the
        # moment it frees the slot, so a request that is admitted and
        # finishes within one step() (max_new_tokens=1) cannot vanish from
        # run_until_done()'s returned list
        self._completed: Dict[int, Request] = {}
        # rid_start: disjoint rid ranges per replica when a ChipFarm fans
        # one request stream across several engines (serving.farm)
        self._rid = itertools.count(rid_start)

    # -- delegation: the model half lives on the runner -----------------
    @property
    def cfg(self) -> ModelConfig:
        return self.runner.cfg

    @property
    def params(self):
        return self.runner.params

    @property
    def max_seq(self) -> int:
        return self.runner.max_seq

    @property
    def temperature(self) -> float:
        return self.runner.temperature

    @property
    def mesh(self):
        return self.runner.mesh

    @property
    def param_axes(self):
        return self.runner.param_axes

    @property
    def plan(self):
        return self.runner.plan

    @property
    def expert_chips(self):
        return self.runner.expert_chips

    @property
    def crossbar(self) -> Optional[CrossbarMode]:
        return self.runner.crossbar

    @property
    def programmed(self):
        return self.runner.programmed

    @property
    def uptime_s(self) -> float:
        return self.runner.uptime_s

    def verify_crossbar_coverage(self) -> None:
        self.runner.verify_crossbar_coverage()

    def save_artifacts(self, directory: str, slot: Optional[str] = None) -> str:
        return self.runner.save_artifacts(directory, slot=slot)

    def repair_reports(self):
        return self.runner.repair_reports()

    def age(self, dt_s: float) -> None:
        self.runner.age(dt_s)

    def health_check(self, n_probes: Optional[int] = None, seed: int = 0,
                     budget: Optional[float] = None):
        return self.runner.health_check(n_probes=n_probes, seed=seed, budget=budget)

    def compensate(self, n_probes: Optional[int] = None, seed: int = 0) -> None:
        self.runner.compensate(n_probes=n_probes, seed=seed)

    def hot_swap(self, directory: str, slot: Optional[str] = None) -> None:
        self.runner.hot_swap(directory, slot=slot)

    def refresh(self, directory: Optional[str] = None) -> Optional[str]:
        return self.runner.refresh(directory)

    # ------------------------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int = 16,
        eos_id: Optional[int] = None,
        truncate: bool = False,
        on_token: Optional[Callable[[Request, int], None]] = None,
    ) -> int:
        prompt = np.asarray(prompt)
        # refuse over-length prompts at submit time (not deep in _admit
        # mid-serving) unless truncation was explicitly allowed
        self.runner.check_prompt(prompt, truncate)
        req = Request(
            next(self._rid), prompt, max_new_tokens, eos_id,
            truncate=truncate, on_token=on_token,
        )
        self.pending.append(req)
        return req.rid

    def _admit(self):
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.pending:
                continue
            req = self.pending.pop(0)
            self.cache, p, lt, first = self.runner.admit_slot(self.cache, slot, req)
            self.pos[slot] = p
            self.last_tok[slot] = lt
            if first is not None:
                req.generated.append(first)
                if req.on_token is not None:
                    req.on_token(req, first)
            self.slots[slot] = req

    # ------------------------------------------------------------------
    def step(self) -> int:
        """Admit pending requests and advance every occupied slot one token.

        Finished requests are recorded in the completion ledger as their
        slots free.  Returns the number of active slots advanced."""
        self._admit()
        active = [i for i in range(self.max_batch) if self.slots[i] is not None]
        if not active:
            return 0
        logits, self.cache = self.runner.decode(self.last_tok, self.pos, self.cache)
        nxt = self.runner.sample(logits)
        for i in active:
            req = self.slots[i]
            self.pos[i] += 1
            tok = int(nxt[i])
            req.generated.append(tok)
            self.last_tok[i] = tok
            if req.on_token is not None:
                req.on_token(req, tok)
            if (
                len(req.generated) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)
                or self.pos[i] >= self.max_seq - 1
            ):
                req.done = True
                self._completed[req.rid] = req
                self.slots[i] = None
        return len(active)

    def run_until_done(self, max_ticks: int = 10_000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.pending and all(s is None for s in self.slots):
                break
            self.step()
        # completion ledger + whatever is still in flight at the tick
        # budget: nothing is lost, even a request admitted and finished
        # inside a single step()
        out = dict(self._completed)
        for s in self.slots:
            if s is not None:
                out[s.rid] = s
        return sorted(out.values(), key=lambda r: r.rid)
