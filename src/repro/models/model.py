"""Model assembly: embed -> stages (scan over super-blocks) -> norm -> logits.

Entry points:
  * ``init_model(key, cfg)``      -> (params, axes)   [axes: logical shardings]
  * ``forward(params, cfg, inp)`` -> logits            [training]
  * ``loss_fn(params, cfg, batch)``-> scalar CE loss
  * ``init_cache(cfg, B, S)``     -> cache pytree
  * ``prefill(params, cfg, inp, cache)``  -> (last_logits, cache)
  * ``decode_step(params, cfg, tok, pos, cache)`` -> (logits, cache)

Layers are stacked per stage on a leading axis and run under ``lax.scan``
(with optional rematerialization), so HLO size is depth-independent — the
multi-pod dry-run and the 1/2-layer roofline extrapolation rely on this.
A decode step carries each stage's cache through its scan as one buffer
and writes each token into it in place; the jitted serving step donates
it, so no layer of the cache is copied.
``inp`` is int tokens (B, S) for ``frontend == "token"`` archs, or
precomputed frame/patch embeddings (B, S, D) for the audio/vlm stubs.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, StageSpec
from repro.device.programmed import bind_artifacts, name_scope
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (
    Init,
    collect_counts,
    current_crossbar,
    embed,
    init_embed,
    init_mlp,
    lm_head,
    mlp,
    record_count,
    rms_norm,
    shard,
    softcap,
)


def _stage_artifacts(si: int):
    """Programmed-crossbar artifact subtree for stage ``si``.

    Non-None only when serving under ``crossbar_mode(CrossbarMode(...,
    programmed=...))`` — the program-once steady-state path.  The subtree
    mirrors the stage's stacked params; ``_run_stage`` zips it into the
    layer scan so each iteration binds its parameter slices to the matching
    pre-programmed artifact slices.
    """
    mode = current_crossbar()
    if not mode.enabled or mode.programmed is None:
        return None
    sub = mode.programmed.subtree(f"stage{si}")
    if sub is None:
        return None
    # stage params are layer-stacked; only stacked artifacts can ride the
    # scan (a stray 2-D artifact would crash the per-layer slicing)
    from repro.device.programmed import stacked_only

    return stacked_only(sub)


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def _init_block(ini: Init, cfg: ModelConfig, kind: str, use_moe: bool):
    d = cfg.d_model
    ini.param("norm1", (d,), (None,), init="zeros")
    mixer = ini.sub("mixer")
    if kind.startswith("attn"):
        attn_mod.init_attention(mixer, cfg)
    elif kind == "mamba":
        ssm_mod.init_mamba(mixer, cfg)
    elif kind == "mlstm":
        xlstm_mod.init_mlstm(mixer, cfg)
    elif kind == "slstm":
        xlstm_mod.init_slstm(mixer, cfg)
    else:
        raise ValueError(kind)
    if cfg.post_norm:
        ini.param("norm1_post", (d,), (None,), init="zeros")
    has_ffn = (cfg.d_ff or use_moe) and kind not in ("mlstm", "slstm")
    if has_ffn:
        ini.param("norm2", (d,), (None,), init="zeros")
        ffn = ini.sub("ffn")
        if use_moe:
            moe_mod.init_moe(ffn, cfg)
        else:
            init_mlp(ffn, d, cfg.d_ff, cfg.mlp_kind)
        if cfg.post_norm:
            ini.param("norm2_post", (d,), (None,), init="zeros")


def _apply_block(
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    kind: str,
    use_moe: bool,
    positions: jnp.ndarray,
    cache_entry=None,
    decode_pos=None,
):
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    new_entry = None
    # name_scope pushes the param path components ("mixer"/"ffn", with
    # "stage{i}"/"b{i}" pushed by the callers) so crossbar_linear call sites
    # can address their programmed artifacts by canonical joined name
    with name_scope("mixer"):
        if kind.startswith("attn"):
            h, new_entry = attn_mod.attention_block(
                params["mixer"], h, cfg, kind, positions, cache_entry, decode_pos
            )
        elif kind == "mamba":
            h, new_entry = ssm_mod.mamba_block(
                params["mixer"], h, cfg, cache_entry, decode=decode_pos is not None
            )
        elif kind == "mlstm":
            h, new_entry = xlstm_mod.mlstm_block(
                params["mixer"], h, cfg, cache_entry, decode=decode_pos is not None
            )
        elif kind == "slstm":
            h, new_entry = xlstm_mod.slstm_block(
                params["mixer"], h, cfg, cache_entry, decode=decode_pos is not None
            )
    if decode_pos is not None and new_entry is not None:
        # a decoding block's cache is its LayerCache: attention writes its
        # token through it, a recurrent block's new state goes over its layer
        cache_entry.overwrite(new_entry)
        new_entry = None
    if cfg.post_norm:
        h = rms_norm(h, params["norm1_post"], cfg.norm_eps)
    x = x + h

    if "norm2" in params:
        h = rms_norm(x, params["norm2"], cfg.norm_eps)
        with name_scope("ffn"):
            if use_moe:
                h = moe_mod.moe_ffn(params["ffn"], h, cfg)
            else:
                h = mlp(params["ffn"], h, cfg.mlp_kind)
        if cfg.post_norm:
            h = rms_norm(h, params["norm2_post"], cfg.norm_eps)
        x = x + h
    return x, new_entry


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, kind: str, batch: int, seq: int, dtype):
    if kind.startswith("attn"):
        return attn_mod.init_attention_cache(cfg, batch, seq, dtype)
    if kind == "mamba":
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    return xlstm_mod.init_xlstm_cache(cfg, kind, batch)


def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16):
    """Cache pytree: list per stage of {b<i>: stacked (repeats, ...)}."""
    stages = []
    for spec in cfg.stages:
        entry = {}
        for i, kind in enumerate(spec.kinds):
            one = _block_cache(cfg, kind, batch, seq, dtype)
            entry[f"b{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (spec.repeats,) + a.shape), one
            )
        stages.append(entry)
    return stages


def cache_axes(cfg: ModelConfig):
    """Logical-axis tree parallel to ``init_cache`` (for input shardings).

    Names: cache_batch (DP when divisible), cache_seq (DP when the batch is
    not shardable — the long_500k sequence-parallel layout), kv_heads /
    heads / d_inner (model axis).
    """

    def block_axes(kind: str):
        if kind.startswith("attn"):
            if cfg.kv_lora_rank:
                return {
                    "latent": ("layers", "cache_batch", "cache_seq", None),
                    "k_rope": ("layers", "cache_batch", "cache_seq", None, None),
                }
            return {
                "k": ("layers", "cache_batch", "cache_seq", "kv_heads", None),
                "v": ("layers", "cache_batch", "cache_seq", "kv_heads", None),
            }
        if kind == "mamba":
            return {
                "h": ("layers", "cache_batch", "d_inner", None),
                "conv": ("layers", "cache_batch", None, "d_inner"),
            }
        if kind == "mlstm":
            return {
                "C": ("layers", "cache_batch", "heads", None, None),
                "n": ("layers", "cache_batch", "heads", None),
            }
        return {
            "c": ("layers", "cache_batch", "heads", None),
            "n": ("layers", "cache_batch", "heads", None),
            "h": ("layers", "cache_batch", "heads", None),
        }

    return [
        {f"b{i}": block_axes(kind) for i, kind in enumerate(spec.kinds)}
        for spec in cfg.stages
    ]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_model(key, cfg: ModelConfig, dtype=None, shape_only: bool = False) -> Tuple[Dict, Dict]:
    """Returns (params, axes).  ``shape_only=True`` materializes nothing —
    params are ShapeDtypeStructs (used by the dry-run for 1T-param configs)."""
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    root = Init(key=key, dtype=dtype, shape_only=shape_only)
    if cfg.frontend == "token":
        init_embed(root.sub("embed"), cfg.vocab_size, cfg.d_model)
    for si, spec in enumerate(cfg.stages):
        # Build one layer's params, then stack `repeats` copies with vmap'd init
        def one(k, so=shape_only):
            ini = Init(key=k, dtype=dtype, shape_only=so)
            for i, kind in enumerate(spec.kinds):
                _init_block(ini.sub(f"b{i}"), cfg, kind, spec.moe[i] and cfg.moe_experts > 0)
            return ini.params, ini.axes

        if shape_only:
            shapes, axes = one(key)
            stacked = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((spec.repeats,) + s.shape, s.dtype),
                shapes,
            )
        else:
            keys = jax.random.split(root._next_key(), spec.repeats)
            stacked = jax.vmap(lambda k: one(k, False)[0])(keys)
            axes = one(key, True)[1]
        axes = jax.tree.map(
            lambda a: ("layers",) + a, axes, is_leaf=lambda x: isinstance(x, tuple)
        )
        root.params[f"stage{si}"] = stacked
        root.axes[f"stage{si}"] = axes
    root.param("final_norm", (cfg.d_model,), (None,), init="zeros")
    if not cfg.tie_embeddings or cfg.frontend != "token":
        root.param("head", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"), scale=cfg.d_model**-0.5)
    return root.params, root.axes


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _run_stage(
    params_stage,
    x,
    cfg: ModelConfig,
    spec: StageSpec,
    positions,
    cache_stage=None,
    decode_pos=None,
    remat: bool = False,
    artifacts_stage=None,
):
    def layer(h, lp, ap, entries):
        # bind this layer's programmed-crossbar artifacts (sliced in lockstep
        # with the params) so crossbar_linear serves steady-state; keys are
        # joined under the caller's "stage{i}" name scope
        with bind_artifacts(ap), collect_counts() as counts:
            new_entries = {}
            for i, kind in enumerate(spec.kinds):
                with name_scope(f"b{i}"):
                    h, new_entries[f"b{i}"] = _apply_block(
                        lp[f"b{i}"], h, cfg, kind,
                        bool(spec.moe[i]) and cfg.moe_experts > 0,
                        positions, entries[f"b{i}"] if entries is not None else None,
                        decode_pos,
                    )
            if decode_pos is None and h.shape[1] > 1:
                # sequence-parallel residual stream: the layer-boundary carries the
                # scan backward must save shrink by the model-axis extent
                h = shard(h, "batch", "act_seq", None)
        return h, new_entries, counts

    if decode_pos is not None:
        # decode: the stage's cache is one buffer in the carry, and the scan
        # runs over layer indices.  Each block writes into its layer in place
        # through a LayerCache.  Each layer's counts leave the scan as outputs.
        def body(carry, xs):
            h, stage = carry
            lp, li, ap = xs
            views = {b: attn_mod.LayerCache(c, li, decode_pos) for b, c in stage.items()}
            h, _, counts = layer(h, lp, ap, views)
            return (h, {b: v.stack for b, v in views.items()}), counts

        carry, xs = (x, cache_stage), (params_stage, jnp.arange(spec.repeats), artifacts_stage)
    else:
        # forward and prefill: each layer's cache entry (prefill) is scanned
        # in and its filled entry scanned out, with its counts
        def body(h, xs):
            lp, cache_layer, ap = xs
            h, new_entries, counts = layer(h, lp, ap, cache_layer)
            return h, (new_entries if cache_layer is not None else None, counts)

        carry, xs = x, (params_stage, cache_stage, artifacts_stage)

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.scan_layers:
        carry, ys = jax.lax.scan(body, carry, xs)
    else:
        # unrolled path (roofline depth variants): every layer appears in the
        # HLO so cost_analysis counts true totals
        outs = []
        for r in range(spec.repeats):
            carry, y = body(carry, jax.tree.map(lambda a: a[r], xs))
            outs.append(y)
        ys = jax.tree.map(lambda *y: jnp.stack(y), *outs)
    if decode_pos is not None:
        (x, new_cache), counts = carry, ys
    else:
        x, (new_cache, counts) = carry, ys
    for name, per_layer in counts.items():
        record_count(name, jnp.sum(per_layer, axis=0))
    return x, new_cache


def _embed_input(params, cfg: ModelConfig, inp) -> jnp.ndarray:
    if cfg.frontend == "token":
        return embed(params["embed"], inp, cfg.embed_scale, cfg.d_model)
    # audio/vlm stub: precomputed frame/patch embeddings
    x = inp.astype(jnp.dtype(cfg.param_dtype))
    return shard(x, "batch", None, None)


def _logits(params, cfg: ModelConfig, x) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings and cfg.frontend == "token":
        # the tied head serves from the transposed artifact that
        # program_model(tie_lm_head=True) binds under the embedding's name
        return lm_head(
            params["embed"]["tokens"], x, tied=True, cap=cfg.logit_softcap,
            name="embed/tokens",
        )
    return lm_head(params["head"], x, tied=False, cap=cfg.logit_softcap, name="head")


def forward(params, cfg: ModelConfig, inp, positions=None) -> jnp.ndarray:
    """Full-sequence forward (training). Returns logits (B, S, V)."""
    x = _embed_input(params, cfg, inp)
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    for si, spec in enumerate(cfg.stages):
        with name_scope(f"stage{si}"):
            x, _ = _run_stage(
                params[f"stage{si}"], x, cfg, spec, positions, remat=cfg.remat,
                artifacts_stage=_stage_artifacts(si),
            )
    return _logits(params, cfg, x)


LOSS_CHUNK = 512  # sequence chunking bounds the live (B, c, V) logits buffer


def loss_fn(params, cfg: ModelConfig, batch) -> jnp.ndarray:
    """Next-token cross entropy. batch: {"inputs": ..., "targets": (B, S)}.

    The LM head + softmax run chunked over the sequence: materializing full
    (B, S, V) logits for a 256k vocab at 4k x 256 tokens would be ~0.5 TB
    even in bf16; chunking keeps the live buffer at (B, c, V).
    """
    x = _embed_input(params, cfg, batch["inputs"])
    S = x.shape[1]
    positions = jnp.arange(S)
    for si, spec in enumerate(cfg.stages):
        with name_scope(f"stage{si}"):
            x, _ = _run_stage(
                params[f"stage{si}"], x, cfg, spec, positions, remat=cfg.remat,
                artifacts_stage=_stage_artifacts(si),
            )
    targets = batch["targets"]
    mask = batch.get("mask", jnp.ones(targets.shape, jnp.float32))

    c = min(LOSS_CHUNK, S)
    if S % c != 0:
        c = S
    nc = S // c
    B = x.shape[0]

    def _one_chunk(xc, tc, mc):
        logits = _logits(params, cfg, xc).astype(jnp.float32)
        # one-hot contraction keeps the vocab dim sharded (take_along_axis
        # would gather the full (B, c, V) logp onto every model shard)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.sum(logits * jax.nn.one_hot(tc, logits.shape[-1], dtype=logits.dtype), -1)
        return jnp.sum((lse - lab) * mc)

    def chunk_nll(carry, inp):
        xc, tc, mc = inp  # (B, c, D), (B, c), (B, c)
        # checkpoint: otherwise every chunk's (B, c, V) logp is saved at once
        return carry + jax.checkpoint(_one_chunk)(xc, tc, mc), None

    xs = (
        x.reshape(B, nc, c, -1).transpose(1, 0, 2, 3),
        targets.reshape(B, nc, c).transpose(1, 0, 2),
        mask.reshape(B, nc, c).transpose(1, 0, 2),
    )
    total, _ = jax.lax.scan(chunk_nll, jnp.zeros((), jnp.float32), xs)
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def prefill(params, cfg: ModelConfig, inp, cache):
    """Process the prompt, fill the cache; returns (last_logits, cache)."""
    x = _embed_input(params, cfg, inp)
    S = x.shape[1]
    positions = jnp.arange(S)
    new_cache = []
    for si, spec in enumerate(cfg.stages):
        with name_scope(f"stage{si}"):
            x, nc = _run_stage(
                params[f"stage{si}"], x, cfg, spec, positions, cache_stage=cache[si],
                remat=False, artifacts_stage=_stage_artifacts(si),
            )
        new_cache.append(nc)
    logits = _logits(params, cfg, x[:, -1:])
    return logits[:, 0], new_cache


def decode_step(params, cfg: ModelConfig, inp, pos, cache):
    """One decode step at position ``pos`` — scalar, or (B,) per-slot
    positions for continuous batching.  Returns (logits, cache)."""
    x = _embed_input(params, cfg, inp)  # (B, 1) tokens or (B, 1, D) embeds
    pos = jnp.asarray(pos)
    positions = pos[:, None] if pos.ndim == 1 else jnp.asarray([0]) + pos
    new_cache = []
    for si, spec in enumerate(cfg.stages):
        with name_scope(f"stage{si}"):
            x, nc = _run_stage(
                params[f"stage{si}"], x, cfg, spec, positions, cache_stage=cache[si],
                decode_pos=pos, remat=False, artifacts_stage=_stage_artifacts(si),
            )
        new_cache.append(nc)
    logits = _logits(params, cfg, x)
    return logits[:, 0], new_cache
