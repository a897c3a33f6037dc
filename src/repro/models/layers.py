"""Core layers: parameter system, sharding helpers, norms, MLPs, RoPE,
embeddings — pure functional JAX (no flax), pytree params.

Parameter/sharding system
-------------------------
``Init`` collects parameters and their *logical axes* simultaneously; logical
axes map to mesh axes via ``LOGICAL_RULES`` ("vocab"/"heads"/"mlp"/"experts"
-> "model"; "batch" -> ("pod","data"); everything else replicated).  The
active mesh is held in a context (``use_mesh``) so the same model code runs
on a single CPU device (tests), the 16x16 production mesh, and the 2x16x16
multi-pod mesh without modification.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Mesh context + logical axis rules
# ---------------------------------------------------------------------------

_CTX = threading.local()

LOGICAL_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "d_inner": "model",
    "seq_shard": ("pod", "data"),  # long-context cache sequence sharding
    "act_seq": "model",  # sequence-parallel residual stream between blocks
    # expert-TP decode layout (weights-stationary serving; see moe.py):
    "moe_dm": None,  # wi contraction dim; "model" under expert_tp
    "moe_ff": None,  # wo contraction dim; "model" under expert_tp
}


def current_mesh() -> Optional[Mesh]:
    return getattr(_CTX, "mesh", None)


def current_overrides() -> Dict[str, Any]:
    return getattr(_CTX, "overrides", {})


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], overrides: Optional[Dict[str, Any]] = None):
    """Install the active mesh and optional per-config logical-rule overrides.

    Overrides support per-architecture layouts, e.g. a 350M model on a fixed
    (data, model) mesh is fastest as pure DP: {"batch": ("pod", "data",
    "model"), "vocab": None, "d_inner": None, ...} treats the model axis as
    extra data parallelism (EXPERIMENTS.md §Perf, xlstm hillclimb).
    """
    prev = getattr(_CTX, "mesh", None)
    prev_ov = getattr(_CTX, "overrides", {})
    _CTX.mesh = mesh
    _CTX.overrides = dict(overrides or {})
    try:
        yield
    finally:
        _CTX.mesh = prev
        _CTX.overrides = prev_ov


def layout_overrides(cfg) -> Dict[str, Any]:
    """Per-config logical-rule overrides (see ModelConfig.layout)."""
    if getattr(cfg, "layout", "") == "pure_dp":
        return {
            "batch": ("pod", "data", "model"),
            "seq_shard": ("pod", "data", "model"),
            "vocab": None,
            "heads": None,
            "kv_heads": None,
            "mlp": None,
            "d_inner": None,
            "experts": None,
            "act_seq": None,
        }
    if getattr(cfg, "layout", "") == "ep_only":
        # Expert-parallel-only serving: the MoE expert banks shard over
        # "model"; every other tensor (and every activation constraint)
        # stays replicated.  The digital parts of the graph then compile
        # identically to single-device, which makes programmed crossbar
        # serving on a mesh *bit-identical* to the single-device chip —
        # the distributed test tier pins exactly this
        # (tests/test_sharded_artifacts.py).
        return {
            "batch": None,
            "seq_shard": None,
            "vocab": None,
            "heads": None,
            "kv_heads": None,
            "mlp": None,
            "d_inner": None,
            "act_seq": None,
        }
    if getattr(cfg, "layout", "") == "expert_tp":
        # Weights-stationary MoE serving: experts sharded over "data",
        # expert FFN contraction dims TP-sharded over "model" — no FSDP
        # weight gathers at decode (the paper's in-situ principle at
        # cluster scale; EXPERIMENTS.md §Perf, deepseek decode).
        return {"experts": "data", "moe_dm": "model", "moe_ff": "model"}
    return {}


def _resolve_axis(logical: Optional[str], mesh: Mesh):
    if logical is None:
        return None
    ov = current_overrides()
    rule = ov[logical] if logical in ov else LOGICAL_RULES.get(logical)
    if rule is None:
        return None
    if isinstance(rule, tuple):
        present = tuple(a for a in rule if a in mesh.axis_names)
        return present if present else None
    return rule if rule in mesh.axis_names else None


def pspec(axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> P:
    mesh = mesh or current_mesh()
    if mesh is None:
        return P()
    return P(*[_resolve_axis(a, mesh) for a in axes])


def dividing_entry(dim: int, ax, mesh: Mesh):
    """Largest usable sharding for one dim: the full entry when it divides,
    else the longest *prefix* of a tuple entry that divides (e.g. batch 32
    on ("pod","data","model") -> ("pod","data")), else None."""
    if ax is None:
        return None
    axes = ax if isinstance(ax, tuple) else (ax,)
    for end in range(len(axes), 0, -1):
        size = int(np.prod([mesh.shape[a] for a in axes[:end]]))
        if size > 1 and dim % size == 0:
            prefix = axes[:end]
            return prefix if isinstance(ax, tuple) else prefix[0]
    return None


def shard(x: jnp.ndarray, *axes: Optional[str]) -> jnp.ndarray:
    """Apply a sharding constraint by logical axes (no-op without a mesh;
    non-dividing dims fall back to the largest dividing prefix)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = pspec(axes, mesh)
    fixed = [dividing_entry(dim, ax, mesh) for dim, ax in zip(x.shape, spec)]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


# ---------------------------------------------------------------------------
# Parameter initialization with collected PartitionSpecs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Init:
    """Collects params and their logical-axis tuples in parallel trees.

    With ``shape_only=True`` no arrays are materialized — params are
    ShapeDtypeStructs.  The dry-run uses this to derive shardings for
    trillion-parameter configs without allocating anything.
    """

    key: jax.Array
    dtype: Any = jnp.float32
    shape_only: bool = False
    params: Dict[str, Any] = dataclasses.field(default_factory=dict)
    axes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def _next_key(self):
        if self.shape_only:
            return self.key
        self.key, sub = jax.random.split(self.key)
        return sub

    def param(
        self,
        name: str,
        shape: Tuple[int, ...],
        axes: Tuple[Optional[str], ...],
        init: str = "normal",
        scale: Optional[float] = None,
    ):
        assert len(shape) == len(axes), (name, shape, axes)
        if self.shape_only:
            v = jax.ShapeDtypeStruct(shape, self.dtype)
        else:
            k = self._next_key()
            if init == "normal":
                s = scale if scale is not None else (shape[0] ** -0.5 if shape else 1.0)
                v = jax.random.normal(k, shape, self.dtype) * jnp.asarray(s, self.dtype)
            elif init == "zeros":
                v = jnp.zeros(shape, self.dtype)
            elif init == "ones":
                v = jnp.ones(shape, self.dtype)
            else:
                raise ValueError(init)
        self.params[name] = v
        self.axes[name] = axes
        return v

    def sub(self, name: str) -> "Init":
        child = Init(key=self._next_key(), dtype=self.dtype, shape_only=self.shape_only)
        self.params[name] = child.params
        self.axes[name] = child.axes
        return child


def axes_to_pspecs(axes_tree, mesh: Mesh):
    """Map a tree of logical-axis tuples to a tree of PartitionSpecs.

    Dims that do not divide their mesh axes are replicated (e.g. smollm's 15
    heads on a 16-way model axis).  Shapes are unknown here, so divisibility
    is checked later against the actual arrays via ``named_sharding_tree``.
    """
    return jax.tree.map(
        lambda a: pspec(a, mesh), axes_tree, is_leaf=lambda x: isinstance(x, tuple)
    )


def named_sharding_tree(params_shape_tree, axes_tree, mesh: Mesh):
    """NamedShardings for every param, dropping non-dividing axis entries."""

    def one(shape_struct, axes):
        spec = pspec(axes, mesh)
        shape = shape_struct.shape
        fixed = []
        for dim, ax in zip(shape, spec):
            if ax is None:
                fixed.append(None)
                continue
            size = int(
                np.prod([mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,))])
            )
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree.map(
        one, params_shape_tree, axes_tree, is_leaf=lambda x: isinstance(x, tuple)
    )


# ---------------------------------------------------------------------------
# Norms / activations / MLPs
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap)


def init_mlp(ini: Init, d_model: int, d_ff: int, kind: str):
    if kind in ("swiglu", "geglu"):
        ini.param("wi", (d_model, 2 * d_ff), ("embed", "mlp"))
    else:
        ini.param("wi", (d_model, d_ff), ("embed", "mlp"))
    ini.param("wo", (d_ff, d_model), ("mlp", "embed"))


def mlp(params, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    # wi/wo route through crossbar_linear so an enabled CrossbarMode (and
    # the programmed/repaired artifact path) covers the FFN, not just the
    # attention projections; with the mode disabled this is a plain matmul
    h = crossbar_linear(x, params["wi"], name="wi")
    h = shard(h, "batch", None, "mlp")
    if kind in ("swiglu", "geglu"):
        u, g = jnp.split(h, 2, axis=-1)
        act = jax.nn.silu(g) if kind == "swiglu" else jax.nn.gelu(g)
        h = u * act
    elif kind == "gelu":
        h = jax.nn.gelu(h)
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        raise ValueError(kind)
    y = crossbar_linear(h, params["wo"], name="wo")
    return shard(y, "batch", None, None)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, m: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: ``0.1 m ln(scale) + 1`` for a
    context stretched ``scale`` times, 1 where it is not stretched."""
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max_pos: int) -> np.ndarray:
    """YaRN's rope frequencies, as DeepSeek-V2's reference code defines them
    (``DeepseekV2YarnRotaryEmbedding``): the dimensions that turn more than
    ``beta_fast`` = 32 times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` = 1 times are
    divided by ``factor``, with a linear ramp between
    (``yarn_find_correction_range``, ``yarn_linear_ramp_mask``).  Its cos
    and sin are scaled by ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``, which is 1 where the two are
    equal; both betas and the equality hold in every DeepSeek-V2 config."""
    beta_fast, beta_slow = 32.0, 1.0

    def correction_dim(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    keep = 1.0 - ramp  # 1 where the original frequency stays
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               inv_freq=None) -> jnp.ndarray:
    """x: (..., S, H, D) with positions (..., S) or (S,).  ``inv_freq``
    (D/2,) replaces the plain frequencies (YaRN)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if inv_freq is None else jnp.asarray(inv_freq)  # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def init_embed(ini: Init, vocab: int, d_model: int):
    ini.param("tokens", (vocab, d_model), ("vocab", "embed"), scale=0.02)


def embed(params, tokens: jnp.ndarray, scale: bool, d_model: int) -> jnp.ndarray:
    x = params["tokens"][tokens]
    x = shard(x, "batch", None, None)
    if scale:
        x = x * jnp.asarray(d_model**0.5, x.dtype)
    return x


def lm_head(
    table_or_w,
    x: jnp.ndarray,
    tied: bool,
    cap: float = 0.0,
    name: Optional[str] = None,
) -> jnp.ndarray:
    # the LM head is the model's largest single projection; routing it
    # through crossbar_linear completes full-model crossbar coverage.  A
    # *tied* head multiplies a transpose of the embedding table — the
    # transpose view has no stable object identity, but it has a stable
    # *name*, so ``program_model(tie_lm_head=True)`` compiles the transpose
    # once at deploy time and name-keyed lookup serves it here; without an
    # artifact the per-call crossbar path programs the transpose like any
    # other unprogrammed projection.
    w = table_or_w.T if tied else table_or_w
    logits = crossbar_linear(x, w, name=name)
    logits = shard(logits, "batch", None, "vocab")
    if cap:
        logits = softcap(logits.astype(jnp.float32), cap)
    return logits


# ---------------------------------------------------------------------------
# CrossbarLinear — the paper's technique as a first-class serving feature
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CrossbarMode:
    """When enabled, every weight-bearing matmul — attention projections,
    dense-MLP wi/wo, the MoE router/experts/shared experts, and the LM head
    (tied or untied; a tied head runs the embedding transpose, see
    ``lm_head``) — runs through the Newton bit-sliced crossbar datapath
    (Pallas kernel; interpret-mode on CPU) instead of XLA matmul; only
    activation-activation products (attention scores/values) stay digital
    (tests/test_models_smoke.py pins the coverage on dense and MoE
    configs).  ``shard_map`` expert-/tensor-parallel bodies serve too:
    artifacts shard with the weights they shadow
    (``device.programmed.shard_artifacts``), the bodies rebind rank-local
    slices by name, and expert-parallel serving stays bit-identical to
    single-device (tests/test_sharded_artifacts.py).

    ``device`` (a ``repro.device.DeviceConfig``) additionally routes the
    matmul through the memristor non-ideality pipeline — stuck cells,
    programming variation, drift, IR drop — so end-to-end model accuracy
    under realistic devices is one context manager away.

    ``programmed`` (a ``repro.device.programmed.ProgrammedModel``) is the
    program-once steady-state path: projections whose *name* resolves a
    compiled artifact skip quantization-scale reductions, fault redraw and
    write-verify entirely and serve from the fixed programmed chip; names
    without an artifact fall back to the program-every-call path above —
    and, because a silent fallback misreports crossbar coverage and skips
    the device model, every such miss is counted
    (``crossbar_misses()``) and ``strict=True`` turns it into an error."""

    enabled: bool = False
    fast: bool = True  # fused exact kernel (full-resolution ADC)
    device: Optional[Any] = None  # repro.device.DeviceConfig
    programmed: Optional[Any] = None  # repro.device.programmed.ProgrammedModel
    strict: bool = False  # raise on artifact miss when ``programmed`` is set


_CROSSBAR = CrossbarMode()

# Artifact-miss accounting: every crossbar_linear call that falls back to
# per-call programming *while a ProgrammedModel is active* records the name
# it failed to resolve.  Misses are recorded at trace time (a cached jit
# executable traces once), so "zero misses over a traced forward" is the
# invariant tests assert.  Stored as {name: count} — bounded by the number
# of distinct projection names, never by call volume, so a long-running
# eager loop with a persistent miss cannot grow memory.
_MISSES = threading.local()  # .counts: dict[str, int], insertion-ordered


def _record_crossbar_miss(name: str) -> None:
    counts = getattr(_MISSES, "counts", None)
    if counts is None:
        counts = _MISSES.counts = {}
    counts[name] = counts.get(name, 0) + 1


def crossbar_misses() -> Tuple[str, ...]:
    """Distinct names that resolved no artifact under an active
    ProgrammedModel, in first-miss order (``crossbar_miss_counts`` for
    per-name totals)."""
    return tuple(getattr(_MISSES, "counts", {}))


def crossbar_miss_counts() -> Dict[str, int]:
    """{name: times missed} under an active ProgrammedModel."""
    return dict(getattr(_MISSES, "counts", {}))


def reset_crossbar_misses() -> None:
    _MISSES.counts = {}


def restore_crossbar_misses(counts: Dict[str, int]) -> None:
    """Overwrite the miss record with a snapshot from
    ``crossbar_miss_counts`` — for internal traces (e.g. the engine's
    construction-time coverage check) that must not leave their own
    trace-time misses behind for an operator to misread."""
    _MISSES.counts = dict(counts)


def note_crossbar_gap(name: str) -> None:
    """Record that a weight-bearing computation stayed digital under an
    active ProgrammedModel.

    Since per-rank artifact sharding, the ``shard_map`` EP/TP bodies serve
    from rank-local artifact slices, so this fires only when a body finds
    *no* artifact to rebind (a partially-programmed model, a stale store):
    the coverage gap must still be loud — it counts as a miss and raises
    under strict mode, never silently misreporting crossbar coverage.
    No-op when no ProgrammedModel is active (digital/per-call runs are not
    gaps).
    """
    if not _CROSSBAR.enabled or _CROSSBAR.programmed is None:
        return
    from repro.device import programmed as prog

    key = prog.scoped_name(name)
    _record_crossbar_miss(key)
    if _CROSSBAR.strict:
        raise LookupError(
            f"crossbar coverage gap: {key!r} runs digitally inside a mesh-"
            "sharded path — no programmed artifact was bound for it to "
            "rebind per rank (a partially-programmed model or a stale "
            "artifact store); program the missing leaf (program_model "
            "leaf_filter), refresh the store, or drop strict mode."
        )


def current_crossbar() -> CrossbarMode:
    """The active CrossbarMode (the all-default disabled mode when unset)."""
    return _CROSSBAR


@contextlib.contextmanager
def crossbar_mode(mode: CrossbarMode):
    global _CROSSBAR
    prev = _CROSSBAR
    _CROSSBAR = mode
    try:
        yield
    finally:
        _CROSSBAR = prev


def _resolve_crossbar_artifact(name: str, shape) -> Tuple[Optional[str], Optional[Any]]:
    """(canonical key, artifact-or-None) for a scoped name + exact shape —
    the single derivation site for the key, shared by the hit and miss
    paths of ``crossbar_linear``.

    Resolution order: the dynamic ``bind_artifacts`` stack (innermost wins
    — this is where scan-sliced per-layer and per-expert bindings live),
    then the active ``CrossbarMode.programmed`` model's canonical
    ``by_name`` table.
    """
    from repro.device import programmed as prog

    key = prog.scoped_name(name)
    art = prog.active_artifact_for(key, tuple(shape))
    if art is None and _CROSSBAR.programmed is not None:
        art = _CROSSBAR.programmed.lookup(key, tuple(shape))
    return key, art


def lookup_crossbar_artifact(name: str, shape) -> Optional[Any]:
    """Resolve a programmed artifact by scoped name + exact shape (see
    ``_resolve_crossbar_artifact``).  Returns None when the mode is
    disabled or nothing matches.  ``shape`` may be a still-stacked shape
    (the MoE expert path fetches its ``(E, K, N)`` bank this way before
    slicing it)."""
    if not _CROSSBAR.enabled:
        return None
    return _resolve_crossbar_artifact(name, shape)[1]


def _programmed_linear_on_mesh(x: jnp.ndarray, art) -> jnp.ndarray:
    """``programmed_linear(x, art)`` under the active mesh.

    XLA cannot partition a Mosaic kernel, so on a multi-device mesh and
    outside a ``shard_map`` body every device computes the whole projection
    from replicated operands.  That is what the layout asks for only when it
    shards no dense tensor (``ep_only``: the expert banks, the one sharded
    axis, are served inside the EP ``shard_map`` bodies).  Under any other
    layout it would all-gather sharded weights and activations, so it
    raises.  Interpreted kernels (off-TPU, ``kernels.ops._auto_interpret``)
    are plain XLA ops, which XLA partitions itself.
    """
    from repro.device import programmed as prog
    from repro.kernels.ops import _auto_interpret

    mesh = current_mesh()
    if (
        mesh is None
        or mesh.size == 1
        or _auto_interpret()
        or jax.sharding.get_abstract_mesh().manual_axes
    ):
        return prog.programmed_linear(x, art)
    sharded = sorted(
        a for a in LOGICAL_RULES if a != "experts" and _resolve_axis(a, mesh) is not None
    )
    if sharded:
        raise ValueError(
            "programmed crossbar kernels on a multi-device mesh run outside "
            "shard_map only on replicated operands, but this layout shards "
            f"the logical axes {sharded}; serve it with layout='ep_only'"
        )
    return jax.shard_map(
        prog.programmed_linear, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False,
    )(x, art)


def crossbar_linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    name: Optional[str] = None,
    *,
    strict: Optional[bool] = None,
) -> jnp.ndarray:
    """y = x @ w, optionally through the crossbar datapath (W16A16).

    Activations are offset-encoded (crossbar inputs are unsigned; the offset
    is corrected digitally — see ``core.crossbar.signed_vmm_limbs``).

    ``name`` is the call site's local parameter name (e.g. "wq"); joined
    with the ambient ``device.programmed.name_scope`` stack it forms the
    canonical artifact key.  If a programmed artifact resolves for that key
    (via an enclosing ``bind_artifacts`` scope or
    ``CrossbarMode.programmed``), the steady-state program-once path serves
    the call: quantize input -> Pallas kernel -> dequantize, with scales /
    effective cells / correction column sums all precomputed at programming
    time.  Otherwise the weight is programmed on the fly (the per-call
    pipeline) — and if a ProgrammedModel *is* active, that fallback is a
    **miss**: it is counted (``crossbar_misses()``), and ``strict=True``
    (per call, or via ``CrossbarMode.strict``) raises instead of silently
    serving digital-grade results the operator believes are programmed."""
    if not _CROSSBAR.enabled:
        return x @ w
    from repro.kernels import ops as kops

    key = art = None
    if name is not None:
        key, art = _resolve_crossbar_artifact(name, w.shape)
    if art is not None:
        from repro.device import programmed as prog

        # consumption record for the structural name-set check: after a
        # traced forward, ProgrammedModel.verify_consumed compares the
        # emitted name set against exactly these hits
        prog.record_artifact_consumed(key)
        # x passed as-is: programmed_linear offset-encodes in x.dtype before
        # casting, mirroring the fallback below op-for-op (pre-casting bf16
        # activations here would break bit-identity between the two paths)
        return _programmed_linear_on_mesh(x, art).astype(x.dtype)

    if _CROSSBAR.programmed is not None:
        if key is None:
            key = f"<unnamed {tuple(int(d) for d in w.shape)}>"
        _record_crossbar_miss(key)
        strict_now = _CROSSBAR.strict if strict is None else strict
        if strict_now:
            raise LookupError(
                f"crossbar artifact miss: {key!r} (shape "
                f"{tuple(int(d) for d in w.shape)}) resolves no programmed "
                "artifact — the call would silently fall back to per-call "
                "programming.  Program the leaf (program_model leaf_filter / "
                "tie_lm_head), fix the call-site name, or drop strict mode."
            )

    shift = jnp.min(x)
    xs = (x - shift).astype(jnp.float32)  # non-negative
    y = kops.crossbar_matmul(
        xs, w.astype(jnp.float32), device=_CROSSBAR.device, fast=_CROSSBAR.fast
    )
    corr = shift.astype(jnp.float32) * jnp.sum(w.astype(jnp.float32), axis=0)
    return (y + corr).astype(x.dtype)


def scan_bound(body: Callable, weights: Dict[str, Any], xs: Any) -> Any:
    """``body(x_g, w_g)`` for every group g along the leading axis of ``xs``
    and of each of the named ``weights`` (None stays None), stacked.

    Each group's weight slab is a projection of its own (an expert's, a
    head's) and maps onto its own crossbars: where the group-stacked
    artifacts that ``program_layer`` compiles from ``(L, G, K, N)`` leaves
    are bound (layer-sliced by the stage scan), each group's slice is bound
    under the same name for the body's ``crossbar_linear`` calls.  One scan
    keeps the HLO size independent of the group count.
    """
    from repro.device.programmed import bind_artifacts

    arts = {}
    for n, w in weights.items():
        art = None if w is None else lookup_crossbar_artifact(n, w.shape)
        if art is not None:
            arts[n] = art

    def step(carry, inp):
        xg, wg, ag = inp
        with bind_artifacts(ag):
            return carry, body(xg, wg)

    _, y = jax.lax.scan(step, 0, (xs, weights, arts))
    return y


def grouped_linear(x: jnp.ndarray, w: jnp.ndarray, name: str) -> jnp.ndarray:
    """``x[g] @ w[g]`` for every group g: x (G, ..., K), w (G, K, N) ->
    (G, ..., N); on the crossbar each group is a projection of its own
    (MLA's per-head absorbed ``W_uk`` / ``W_uv``, see ``scan_bound``)."""
    if not _CROSSBAR.enabled:
        return jnp.einsum("g...k,gkn->g...n", x, w)
    return scan_bound(lambda xg, ws: crossbar_linear(xg, ws[name], name=name), {name: w}, x)


# ---------------------------------------------------------------------------
# Counters a traced step reports beside its outputs
# ---------------------------------------------------------------------------
#
# ``record_count`` adds an int32 count (a scalar, or one per token) to the
# innermost ``collect_counts`` scope and is a no-op outside one.  The
# serving steps open a scope around the whole model (``ModelRunner``), and
# the layer scan opens one per layer and returns its counts as scan
# outputs, which the stage records summed over its layers
# (``models.model._run_stage``): values traced inside a scan body can leave
# it only that way.

_COUNTS = threading.local()  # .stack: list of {name: int32 array}


@contextlib.contextmanager
def collect_counts():
    stack = getattr(_COUNTS, "stack", None)
    if stack is None:
        stack = _COUNTS.stack = []
    counts: Dict[str, jnp.ndarray] = {}
    stack.append(counts)
    try:
        yield counts
    finally:
        stack.pop()


def record_count(name: str, value) -> None:
    stack = getattr(_COUNTS, "stack", None)
    if not stack:
        return
    counts = stack[-1]
    value = jnp.asarray(value, jnp.int32)
    counts[name] = counts[name] + value if name in counts else value
