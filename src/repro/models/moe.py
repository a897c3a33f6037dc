"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md §4): experts are sharded over the ``model`` mesh axis.  At
the MoE boundary activations are model-replicated (as after any Megatron
row-parallel matmul), so each model rank routes *locally*, computes its own
experts on a capacity-bounded buffer, and the partial outputs are combined
with one all-reduce over ``model`` — the same collective a dense Megatron
FFN needs, and no all-to-all.  (The all-to-all dispatch alternative is
evaluated in EXPERIMENTS.md §Perf.)

Dispatch is sort-based (argsort over N*k expert assignments) rather than the
GShard one-hot-cumsum, keeping transient memory O(N*k) instead of O(N*E) —
at kimi-k2 scale (384 experts) that is the difference between 2 MB and 50 MB
per layer per device.

Shared experts (deepseek-v2) are dense MLPs applied to every token and use
ordinary tensor parallelism outside this module.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (
    Init,
    crossbar_linear,
    current_crossbar,
    current_mesh,
    lookup_crossbar_artifact,
    note_crossbar_gap,
    record_count,
    scan_bound,
    shard,
)


def init_moe(ini: Init, cfg: ModelConfig):
    # the router scores every expert; the banks hold this chip's share
    d, e, f = cfg.d_model, cfg.experts_held, cfg.moe_d_ff
    ini.param("router", (d, cfg.moe_experts), ("moe_dm", None), scale=0.02)
    glu = cfg.mlp_kind in ("swiglu", "geglu")
    # up ("wi") and gate ("wg") projections are separate parameters so the
    # F dim can be TP-sharded (expert_tp layout) without the fused-GLU
    # split-vs-shard hazard.
    # expert_tp shards wi/wg on the D contraction ("moe_dm") and wo on the F
    # contraction ("moe_ff") — distinct names so no tensor maps one mesh
    # axis twice.
    ini.param("wi", (e, d, f), ("experts", "moe_dm", None))
    if glu:
        ini.param("wg", (e, d, f), ("experts", "moe_dm", None))
    ini.param("wo", (e, f, d), ("experts", "moe_ff", "embed"))
    if cfg.moe_shared_experts:
        # Under alltoall dispatch the shared expert runs on the
        # sequence-sharded stream with *replicated* weights (they are small),
        # so the MoE layer needs no activation gather at all; under
        # allreduce dispatch it is a standard TP ("mlp"-sharded) MLP.
        shard_ax = None if cfg.moe_dispatch == "alltoall" else "mlp"
        fs = cfg.moe_d_ff * cfg.moe_shared_experts
        ini.param("shared_wi", (d, fs), ("embed", shard_ax))
        if glu:
            ini.param("shared_wg", (d, fs), ("embed", shard_ax))
        ini.param("shared_wo", (fs, d), (shard_ax, "embed"))


def _act(u, g, kind: str):
    if kind == "swiglu":
        return u * jax.nn.silu(g)
    if kind == "geglu":
        return u * jax.nn.gelu(g)
    if kind == "gelu":
        return jax.nn.gelu(u)
    return jnp.square(jax.nn.relu(u))


def _expert_ffn(h: jnp.ndarray, wi, wg, wo, kind: str) -> jnp.ndarray:
    """h: (E, C, D); wi/wg: (E, D, F); wo: (E, F, D).

    Inside ``shard_map`` bodies the weights are rank-local expert shards;
    per-rank artifact sharding rebinds the matching rank-local artifact
    slices by name before this runs, so the crossbar path below serves
    expert-parallel ranks exactly like the single-device path (each
    expert's (D, F) slab is intact on its owner rank — bit-identical).
    """
    if not current_crossbar().enabled:
        u = jnp.einsum("ecd,edf->ecf", h, wi)
        g = jnp.einsum("ecd,edf->ecf", h, wg) if wg is not None else None
        a = _act(u, g, kind)
        return jnp.einsum("ecf,efd->ecd", a, wo)
    return _expert_ffn_crossbar(h, wi, wg, wo, kind)


def _expert_ffn_crossbar(h: jnp.ndarray, wi, wg, wo, kind: str) -> jnp.ndarray:
    """The expert FFN on the crossbar datapath: one scan over experts.

    Each expert's (D, F) / (F, D) projection is an independent weight slab
    and maps onto its own crossbars, so the batched einsum decomposes into
    per-expert ``crossbar_linear`` calls, each serving from its expert's
    slice of the expert-stacked artifacts where they are bound
    (``layers.scan_bound``); otherwise the per-call pipeline programs each
    expert slice on the fly, exactly like any other unprogrammed
    projection.
    """
    def body(he, ws):
        u = crossbar_linear(he, ws["wi"], name="wi")
        g = crossbar_linear(he, ws["wg"], name="wg") if ws["wg"] is not None else None
        return crossbar_linear(_act(u, g, kind), ws["wo"], name="wo")

    return scan_bound(body, {"wi": wi, "wg": wg, "wo": wo}, h)


# ---------------------------------------------------------------------------
# Per-rank artifact plumbing for shard_map bodies
# ---------------------------------------------------------------------------

def _artifact_shard_inputs(entries):
    """Stage this layer's programmed artifacts for ``shard_map`` passing.

    ``entries``: ``(name, weight, weight_pspec)`` per projection the body
    serves.  For every name that resolves a bound artifact (the stage scan
    binds the layer-sliced banks just outside this call), returns parallel
    dicts: ``arrays`` (the artifact's array leaves — a shard_map input
    pytree), ``specs`` (matching in_specs, derived from the *weight's*
    PartitionSpec so artifact shards track weight shards axis-for-axis) and
    ``templates`` (the global artifacts, closed over for their static aux).
    Names with no artifact are simply absent — the body notes the gap
    loudly if a ProgrammedModel is active.
    """
    from repro.device import programmed as prog

    arrays, specs, templates = {}, {}, {}
    for name, w, wspec in entries:
        if w is None:
            continue
        art = lookup_crossbar_artifact(name, w.shape)
        if art is None:
            continue
        arrays[name] = prog.artifact_arrays(art)
        specs[name] = prog.artifact_shard_specs(art, wspec)
        templates[name] = art
    return arrays, specs, templates


def _rebind_rank_artifacts(templates, arrays):
    """Rebuild rank-local artifacts from shard_map-sliced arrays (inside the
    body) keyed by the same call-site names the global binding used."""
    from repro.device import programmed as prog

    return {n: prog.with_arrays(templates[n], arrays[n]) for n in arrays}


def _dispatch_compute(
    xf: jnp.ndarray,  # (N, D) tokens
    top_idx: jnp.ndarray,  # (N, k) global expert ids
    gates: jnp.ndarray,  # (N, k)
    wi: jnp.ndarray,  # (E_loc, D, F)
    wg,  # (E_loc, D, F) or None
    wo: jnp.ndarray,  # (E_loc, F, D)
    lo: jnp.ndarray,  # first global expert id owned locally
    capacity: int,
    mlp_kind: str,
) -> jnp.ndarray:
    """Capacity-bounded dispatch -> expert FFN -> weighted combine.

    All (token, D)-sized gathers/scatters happen in *slot space* (E_loc * C
    rows), never in assignment space (N * k rows) — at kimi-k2 scale that is
    1.2 GB vs 14 GB of transients per layer.
    """
    N, k = top_idx.shape
    E_loc = wi.shape[0]
    n_slots = E_loc * capacity
    flat_e_glob = top_idx.reshape(-1)
    flat_gate = gates.reshape(-1)
    e_loc = flat_e_glob - lo
    is_local = (e_loc >= 0) & (e_loc < E_loc)
    e_key = jnp.where(is_local, e_loc, E_loc)  # non-local -> overflow bucket
    order = jnp.argsort(e_key, stable=True)
    sorted_e = e_key[order]
    counts = jnp.bincount(e_key, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * k, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep = (sorted_e < E_loc) & (pos < capacity)
    slot = jnp.where(keep, sorted_e.astype(jnp.int32) * capacity + pos, n_slots)
    token_of = (order // k).astype(jnp.int32)

    # slot -> source token / gate (index arrays only; O(E*C + N*k) ints)
    tok_slot = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(token_of)
    gate_slot = (
        jnp.zeros((n_slots + 1,), flat_gate.dtype)
        .at[slot]
        .set(flat_gate[order] * keep.astype(flat_gate.dtype))
    )
    buf = xf[tok_slot[:n_slots]].reshape(E_loc, capacity, -1)
    out = _expert_ffn(buf, wi, wg, wo, mlp_kind)
    contrib = out.reshape(n_slots, -1) * gate_slot[:n_slots, None].astype(out.dtype)
    y = jnp.zeros_like(xf).at[tok_slot[:n_slots]].add(contrib.astype(xf.dtype))
    return y


def _route(x: jnp.ndarray, router_w: jnp.ndarray, cfg: ModelConfig):
    # the router is a weight-bearing projection like any other: under an
    # enabled CrossbarMode it runs on the crossbar datapath (programmed or
    # per-call), so routing decisions are made from the analog logits the
    # deployed chip would actually produce.  Inside shard_map EP bodies the
    # router weight is replicated and its (rebound) artifact serves whole.
    logits = crossbar_linear(x, router_w.astype(x.dtype), name="router").astype(
        jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return idx, gates.astype(x.dtype), probs


def _capacity(n_tokens: int, cfg: ModelConfig, n_local_experts: int) -> int:
    # a factor of moe_experts / moe_top_k gives n_tokens rows, which drops
    # nothing: top-k picks distinct experts
    c = n_tokens * cfg.moe_top_k / max(1, cfg.moe_experts) * cfg.moe_capacity_factor
    return max(8, int(math.ceil(c / 8) * 8))


def _record_dispatch(idx: jnp.ndarray, n_held: int, capacity: int) -> None:
    """Count what one layer's dispatch does on this chip: per token, the
    routed assignments that land on its held experts (``moe_held``, shaped
    like the tokens, so that the runner can leave out the rows that carry
    no request), the rows its expert projections compute (``moe_rows``),
    and the held assignments past capacity, which contribute nothing
    (``moe_dropped``)."""
    record_count("moe_held", jnp.sum(idx < n_held, axis=-1))
    record_count("moe_rows", n_held * capacity)
    per_expert = jnp.bincount(idx.reshape(-1), length=n_held)
    record_count("moe_dropped", jnp.sum(per_expert) - jnp.sum(jnp.minimum(per_expert, capacity)))


def _dispatch_indices(top_idx, gates, n_experts: int, capacity: int):
    """Slot assignment shared by both EP dispatches.

    Returns (tok_slot, gate_slot) with ``n_experts * capacity`` slots;
    overflow assignments drop (capacity semantics, GShard)."""
    N, k = top_idx.shape
    n_slots = n_experts * capacity
    flat_e = top_idx.reshape(-1)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=n_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N * k, dtype=jnp.int32) - starts[sorted_e].astype(jnp.int32)
    keep = pos < capacity
    slot = jnp.where(keep, sorted_e.astype(jnp.int32) * capacity + pos, n_slots)
    token_of = (order // k).astype(jnp.int32)
    tok_slot = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(token_of)
    gate_slot = (
        jnp.zeros((n_slots + 1,), flat_gate.dtype)
        .at[slot]
        .set(flat_gate[order] * keep.astype(flat_gate.dtype))
    )
    return tok_slot[:n_slots], gate_slot[:n_slots]


def _moe_alltoall(params, x, cfg: ModelConfig, mesh, batch_axes):
    """GShard-style EP: tokens stay sequence-sharded over ``model``; the
    dispatch all-to-all moves only routed token copies (N_loc * k * D),
    not the full activation — ~8x less traffic than replicated-token EP at
    kimi-k2 scale (EXPERIMENTS.md §Perf)."""
    B, S, D = x.shape
    E = cfg.moe_experts
    n_ranks = int(mesh.shape["model"])
    E_loc = E // n_ranks
    dp = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    n_loc = (B // dp if B % dp == 0 else B) * (S // n_ranks)
    cap = _capacity(n_loc, cfg, E_loc)
    x_spec = (
        P(batch_axes, "model", None) if B % dp == 0 else P(None, "model", None)
    )

    wg = params.get("wg")
    e_spec = P("model", None, None)
    # per-rank artifact sharding: the expert banks' artifacts slice along E
    # with the weights (router stays replicated, its artifact whole), so the
    # body serves programmed from rank-local chips instead of going digital
    from repro.device.programmed import bind_artifacts

    arts, aspecs, tmpl = _artifact_shard_inputs((
        ("router", params["router"], P(None, None)),
        ("wi", params["wi"], e_spec),
        ("wg", wg, e_spec),
        ("wo", params["wo"], e_spec),
    ))

    def body(xl, rw, wi_l, wg_l, wo_l, arts_l):
        Bl, Sl, _ = xl.shape
        xf = xl.reshape(-1, D)
        with bind_artifacts(_rebind_rank_artifacts(tmpl, arts_l)):
            idx, gates, _ = _route(xl, rw, cfg)
            tok_slot, gate_slot = _dispatch_indices(
                idx.reshape(-1, cfg.moe_top_k), gates.reshape(-1, cfg.moe_top_k), E, cap
            )
            buf = xf[tok_slot]  # (E * cap, D): rows for every (expert, slot)
            # dispatch: slice per destination rank, exchange
            buf = buf.reshape(n_ranks, E_loc * cap, D)
            buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0, tiled=True)
            # now (n_ranks * E_loc * cap, D) = this rank's experts, all sources
            h = buf.reshape(n_ranks, E_loc, cap, D).transpose(1, 0, 2, 3)
            h = h.reshape(E_loc, n_ranks * cap, D)
            out = _expert_ffn(h, wi_l, wg_l, wo_l, cfg.mlp_kind)
        out = out.reshape(E_loc, n_ranks, cap, D).transpose(1, 0, 2, 3)
        out = out.reshape(n_ranks, E_loc * cap, D)
        out = jax.lax.all_to_all(out, "model", split_axis=0, concat_axis=0, tiled=True)
        contrib = out.reshape(E * cap, D) * gate_slot[:, None].astype(out.dtype)
        y = jnp.zeros_like(xf).at[tok_slot].add(contrib.astype(xf.dtype))
        return y.reshape(Bl, Sl, D)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec, P(None, None), e_spec, None if wg is None else e_spec, e_spec,
            aspecs,
        ),
        out_specs=x_spec,
        check_vma=False,
    )(x, params["router"], params["wi"], wg, params["wo"], arts)


def _moe_expert_tp(params, x, cfg: ModelConfig, mesh, batch_axes):
    """Weights-stationary serving EP (layout="expert_tp"): experts sharded
    over "data", expert FFN contraction dims TP-sharded over "model" — the
    paper's in-situ principle at cluster scale: no weight ever moves; only
    the (tiny, at decode) routed activations cross links, via one all-to-all
    over "data" and psum-scatters over "model".  See EXPERIMENTS.md §Perf
    (deepseek-v2 decode hillclimb)."""
    B, S, D = x.shape
    E = cfg.moe_experts
    n_dr = int(mesh.shape["data"])
    n_mr = int(mesh.shape["model"])
    E_dp = E // n_dr
    dp = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    n_loc = (B // dp if B % dp == 0 else B) * S
    cap = _capacity(n_loc, cfg, E_dp)
    # tokens: batch over data, D sharded over model (activations tiny)
    x_spec = P(batch_axes, None, "model") if B % dp == 0 else P(None, None, "model")

    wg = params.get("wg")
    wspec_i = P("data", "model", None)
    wspec_o = P("data", "model", None)
    # per-rank artifact sharding, TP flavor: every projection here contracts
    # over a mesh-sharded dim, so each rank holds *rows of the global chip*
    # (experts additionally sharded over "data").  Rank-local artifacts
    # serve partial sums — physically, row-split crossbar tiles whose
    # results the existing psum/psum_scatter collectives accumulate
    # digitally, exactly the paper's inter-tile reduction at cluster scale.
    from repro.device.programmed import programmed_linear as _plin

    arts, aspecs, tmpl = _artifact_shard_inputs((
        ("router", params["router"], P("model", None)),
        ("wi", params["wi"], wspec_i),
        ("wg", wg, wspec_i),
        ("wo", params["wo"], wspec_o),
    ))

    def body(xl, rw_l, wi_l, wg_l, wo_l, arts_l):
        # xl: (B_loc, S, D/mr); rw_l: (D/mr, E); wi_l/wg_l: (E_dp, D/mr, F);
        # wo_l: (E_dp, F/mr, D)
        from repro.device import programmed as _prog

        local = _rebind_rank_artifacts(tmpl, arts_l)
        for n in local:
            # the TP partial path serves below via programmed_linear directly
            # (crossbar_linear cannot express the colsum override), so record
            # consumption here for the structural name-set check
            _prog.record_artifact_consumed(_prog.scoped_name(n))

        def _partial(xe, we, art):
            # K-sharded programmed partial: the artifact's sliced rows are
            # the rows the global chip programmed (quantization is
            # elementwise in w); the offset correction must use the *local*
            # rows' column sums — sum_r(shift_r * colsum_r) reconstitutes
            # the full correction exactly under the caller's all-reduce
            return _plin(xe, art, colsum=jnp.sum(we.astype(jnp.float32), axis=0))

        def _bank(h, w_l, name):
            # (E_dp, C, K_loc) @ (E_dp, K_loc, N) partial sums, per-expert
            # scan so HLO size stays E-independent; collectives hoisted out
            art = local.get(name)
            if art is None:
                note_crossbar_gap(name)
                return jnp.einsum("ecd,edf->ecf", h, w_l)

            def f(c, xs_):
                he, we, ae = xs_
                return c, _partial(he, we, ae).astype(he.dtype)

            _, u = jax.lax.scan(f, 0, (h, w_l, art))
            return u

        Bl, Sl, Dl = xl.shape
        xf = xl.reshape(-1, Dl)
        if "router" in local:
            part = _partial(xf, rw_l.astype(xf.dtype), local["router"])
        else:
            note_crossbar_gap("router")
            part = (xf @ rw_l.astype(xf.dtype)).astype(jnp.float32)
        logits = jax.lax.psum(part.astype(jnp.float32), "model")
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.moe_top_k)
        gates = (gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)).astype(xf.dtype)
        tok_slot, gate_slot = _dispatch_indices(idx, gates, E, cap)
        buf = xf[tok_slot]  # (E * cap, D/mr)
        buf = buf.reshape(n_dr, E_dp * cap, Dl)
        buf = jax.lax.all_to_all(buf, "data", split_axis=0, concat_axis=0, tiled=True)
        h = buf.reshape(n_dr, E_dp, cap, Dl).transpose(1, 0, 2, 3).reshape(E_dp, n_dr * cap, Dl)
        # expert matmuls: contraction over the model-sharded D, then psum-
        # scatter onto the model-sharded F — weights never move
        u = _bank(h, wi_l, "wi")
        u = jax.lax.psum_scatter(u, "model", scatter_dimension=2, tiled=True)
        if wg_l is not None:
            g = _bank(h, wg_l, "wg")
            g = jax.lax.psum_scatter(g, "model", scatter_dimension=2, tiled=True)
        else:
            g = None
        a = _act(u, g, cfg.mlp_kind)  # (E_dp, slots, F/mr)
        out = _bank(a, wo_l, "wo")  # partial over F -> full D
        out = jax.lax.psum_scatter(out, "model", scatter_dimension=2, tiled=True)
        # back to sources
        out = out.reshape(E_dp, n_dr, cap, Dl).transpose(1, 0, 2, 3).reshape(n_dr, E_dp * cap, Dl)
        out = jax.lax.all_to_all(out, "data", split_axis=0, concat_axis=0, tiled=True)
        contrib = out.reshape(E * cap, Dl) * gate_slot[:, None].astype(out.dtype)
        y = jnp.zeros_like(xf).at[tok_slot].add(contrib.astype(xf.dtype))
        return y.reshape(Bl, Sl, Dl)

    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            x_spec,
            P("model", None),
            wspec_i,
            None if wg is None else wspec_i,
            wspec_o,
            aspecs,
        ),
        out_specs=x_spec,
        check_vma=False,
    )(x, params["router"], params["wi"], wg, params["wo"], arts)
    return y


def moe_ffn(params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D).  Routed experts + optional shared expert."""
    B, S, D = x.shape
    mesh = current_mesh()
    E = cfg.moe_experts
    model_size = int(mesh.shape.get("model", 1)) if mesh is not None else 1
    if mesh is not None:
        from repro.models.layers import _resolve_axis

        if _resolve_axis("experts", mesh) is None and cfg.layout != "expert_tp":
            model_size = 1  # layout override: no EP
    if cfg.experts_held != E and model_size > 1:
        raise ValueError("a chip's share of the experts (moe_experts_held) is served "
                         "without an expert-parallel mesh")

    if (
        cfg.layout == "expert_tp"
        and mesh is not None
        and "data" in mesh.axis_names
        and model_size > 1
        and E % int(mesh.shape["data"]) == 0
        and D % model_size == 0
        and cfg.moe_d_ff % model_size == 0
    ):
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        y = _moe_expert_tp(
            params, shard(x, "batch", None, "moe_dm"), cfg, mesh, batch_axes
        )
        y = shard(y, "batch", None, "moe_dm")
    elif (
        cfg.moe_dispatch == "alltoall"
        and mesh is not None
        and model_size > 1
        and E % model_size == 0
        and S % model_size == 0
    ):
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        y = _moe_alltoall(params, shard(x, "batch", "act_seq", None), cfg, mesh, batch_axes)
    elif mesh is None or model_size == 1 or E % model_size != 0:
        # experts 0..held-1 are this chip's; assignments to the others go
        # to _dispatch_compute's overflow bucket and contribute nothing
        idx, gates, _ = _route(x, params["router"], cfg)
        cap = _capacity(B * S, cfg, E)
        _record_dispatch(idx, cfg.experts_held, cap)
        y = _dispatch_compute(
            x.reshape(-1, D),
            idx.reshape(-1, cfg.moe_top_k),
            gates.reshape(-1, cfg.moe_top_k),
            params["wi"],
            params.get("wg"),
            params["wo"],
            jnp.int32(0),
            cap,
            cfg.mlp_kind,
        ).reshape(B, S, D)
    else:
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp = int(np.prod([mesh.shape[a] for a in batch_axes]))
        n_local = (B * S) // dp if B % dp == 0 else B * S
        cap = _capacity(n_local, cfg, E // model_size)
        x_spec = P(batch_axes, None, None) if B % dp == 0 else P(None, None, None)

        wg = params.get("wg")
        e_spec = P("model", None, None)
        # per-rank artifact sharding: each expert bank's artifact slices
        # along E exactly like its weight, so every rank serves its local
        # experts from the programmed chip — bit-identical to single-device
        # (each expert's (D, F) slab is intact on its owner rank)
        from repro.device.programmed import bind_artifacts

        arts, aspecs, tmpl = _artifact_shard_inputs((
            ("router", params["router"], P(None, None)),
            ("wi", params["wi"], e_spec),
            ("wg", wg, e_spec),
            ("wo", params["wo"], e_spec),
        ))

        def body(xl, rw, wi_l, wg_l, wo_l, arts_l):
            Bl, Sl, _ = xl.shape
            with bind_artifacts(_rebind_rank_artifacts(tmpl, arts_l)):
                idx, gates, _ = _route(xl, rw, cfg)
                rank = jax.lax.axis_index("model")
                lo = rank.astype(jnp.int32) * (E // model_size)
                y = _dispatch_compute(
                    xl.reshape(-1, D),
                    idx.reshape(-1, cfg.moe_top_k),
                    gates.reshape(-1, cfg.moe_top_k),
                    wi_l,
                    wg_l,
                    wo_l,
                    lo,
                    cap,
                    cfg.mlp_kind,
                ).reshape(Bl, Sl, D)
            return jax.lax.psum(y, "model")

        y = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                x_spec, P(None, None), e_spec, None if wg is None else e_spec,
                e_spec, aspecs,
            ),
            out_specs=x_spec,
            check_vma=False,
        )(x, params["router"], params["wi"], wg, params["wo"], arts)

    if cfg.moe_shared_experts:
        if cfg.moe_dispatch == "alltoall":
            # replicated weights, sequence-sharded tokens: zero comm
            xs = shard(x, "batch", "act_seq", None)
        else:
            xs = x
        u = crossbar_linear(xs, params["shared_wi"], name="shared_wi")
        g = (
            crossbar_linear(xs, params["shared_wg"], name="shared_wg")
            if "shared_wg" in params
            else None
        )
        if cfg.moe_dispatch != "alltoall":
            u = shard(u, "batch", None, "mlp")
            g = shard(g, "batch", None, "mlp") if g is not None else None
        h = _act(u, g, cfg.mlp_kind)
        y = y + crossbar_linear(h, params["shared_wo"], name="shared_wo")
    if cfg.moe_dispatch == "alltoall":
        return shard(y, "batch", "act_seq", None)
    return shard(y, "batch", None, None)
