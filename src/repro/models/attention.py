"""Attention variants: GQA (dense + q-chunked), sliding-window local,
softcapped (gemma2), and absorbed multi-head latent attention (MLA,
deepseek-v2) — with KV caches for prefill/decode serving.

Memory strategy: training/prefill attention scans over query chunks
(``Q_CHUNK``), bounding the live score tensor to (B, qc, H, S) regardless of
sequence length; GQA grouping is kept inside the einsum so KV heads are
never materialized repeated.  MLA uses the *absorbed* form — scores are
computed directly against the latent cache, so per-head K/V are never
materialized (this is what makes deepseek-v2 prefill_32k fit).

A decode step writes each token in place on the stage's stacked cache
through a ``LayerCache`` (see ``_cache_write``).  Cache sharding:
``shard_cache`` shards the batch dim over ("pod","data") when it divides,
otherwise (long_500k, batch=1) shards the cache *sequence* dim — decode
attention then reduces over a sharded axis and XLA inserts the
softmax-stable all-reduces.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (
    Init,
    apply_rope,
    crossbar_linear,
    current_mesh,
    grouped_linear,
    pspec,
    rms_norm,
    shard,
    softcap,
    yarn_inv_freq,
    yarn_mscale,
)

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256  # bounds live scores at (B, 256, H, S); see EXPERIMENTS.md §Perf
NEG_INF = -2.3819763e38  # most-negative bf16-representable-ish


def shard_cache(x: jnp.ndarray, batch_axis: int = 0) -> jnp.ndarray:
    """Shard a cache array (B, S, ...), or a stage's layer stack with
    ``batch_axis`` 1: its batch axis over ("pod","data") when divisible,
    else the sequence axis after it (long-context SP)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    from repro.models.layers import _resolve_axis, dividing_entry

    resolved = _resolve_axis("batch", mesh)
    dp_axes = () if resolved is None else (
        resolved if isinstance(resolved, tuple) else (resolved,)
    )
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    spec = [None] * x.ndim
    nb = x.shape[batch_axis]
    b_entry = dividing_entry(nb, dp_axes, mesh) if dp > 1 and nb > 1 else None
    if b_entry is not None:
        spec[batch_axis] = b_entry
    elif dp > 1 and x.shape[batch_axis + 1] % dp == 0:
        spec[batch_axis + 1] = dp_axes
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_attention(ini: Init, cfg: ModelConfig):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_lora_rank:
        ini.param("wq", (d, h * (dh + cfg.qk_rope_dim)), ("embed", "heads"))
        ini.param("w_kv_down", (d, cfg.kv_lora_rank + cfg.qk_rope_dim), ("embed", None))
        ini.param("kv_norm", (cfg.kv_lora_rank,), (None,), init="zeros")
        # the per-head absorbed up-projections, head-major with K and N last:
        # each head's slab is a crossbar projection of its own
        ini.param("w_uk", (h, dh, cfg.kv_lora_rank), ("heads", None, None))
        ini.param("w_uv", (h, cfg.kv_lora_rank, dh), ("heads", None, None))
        ini.param("wo", (h * dh, d), ("heads", "embed"))
    else:
        ini.param("wq", (d, h * dh), ("embed", "heads"))
        ini.param("wk", (d, kv * dh), ("embed", "kv_heads"))
        ini.param("wv", (d, kv * dh), ("embed", "kv_heads"))
        ini.param("wo", (h * dh, d), ("heads", "embed"))


# ---------------------------------------------------------------------------
# GQA core
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B, qc, G, R, dh); k: (B, S, G, dh) -> (B, qc, G, R, S)."""
    return jnp.einsum("bqgrd,bsgd->bqgrs", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: (B, qc, G, R, S); v: (B, S, G, dh) -> (B, qc, G, R, dh)."""
    return jnp.einsum("bqgrs,bsgd->bqgrd", p, v.astype(p.dtype))


def _mask(pos_q, pos_k, window: int):
    m = pos_k[None, :] <= pos_q[:, None]
    if window:
        m &= pos_k[None, :] > (pos_q[:, None] - window)
    return m


def gqa_attention(
    q: jnp.ndarray,  # (B, S, H, dh)
    k: jnp.ndarray,  # (B, Sk, KV, dh)
    v: jnp.ndarray,
    *,
    scale: float,
    window: int = 0,
    attn_cap: float = 0.0,
    q_offset: int = 0,
    chunk: int = Q_CHUNK,
) -> jnp.ndarray:
    B, S, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G, R = KV, H // KV
    qg = q.reshape(B, S, G, R, dh)
    pos_k = jnp.arange(Sk)

    def block(q_blk, start):
        pos_q = q_offset + start + jnp.arange(q_blk.shape[1])
        s = _gqa_scores(q_blk, k) * scale
        if attn_cap:
            s = softcap(s, attn_cap)
        m = _mask(pos_q, pos_k, window)
        s = jnp.where(m[None, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _gqa_out(p, v)

    if S <= chunk:
        out = block(qg, 0)
    else:
        nc = S // chunk
        assert S % chunk == 0, (S, chunk)
        qc = qg.reshape(B, nc, chunk, G, R, dh).transpose(1, 0, 2, 3, 4, 5)

        # checkpoint each chunk: without this the scan's backward saves every
        # chunk's (B, qc, H, S) score tensor simultaneously (flash-attention
        # memory discipline, rematerialized per chunk)
        def body(_, inp):
            q_blk, idx = inp
            return None, jax.checkpoint(block)(q_blk, idx * chunk)

        _, outs = jax.lax.scan(body, None, (qc, jnp.arange(nc)))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, G, R, dh)
    return out.reshape(B, S, H, dh).astype(q.dtype)


def decode_attention(q, k, v, pos, *, scale, window=0, attn_cap=0.0):
    """Single-position decode: q (B, 1, H, dh) against full cache (B, S, KV, dh).

    ``pos`` is the index of the newest token — scalar, or (B,) for
    continuous batching (each slot at its own position); cache entries
    beyond a slot's position are masked.
    """
    B, _, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G, R = KV, H // KV
    qg = q.reshape(B, 1, G, R, dh)
    s = _gqa_scores(qg, k) * scale  # (B,1,G,R,S)
    if attn_cap:
        s = softcap(s, attn_cap)
    pos_k = jnp.arange(Sk)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))  # scalar or per-slot
    m = pos_k[None, :] <= pos_b[:, None]
    if window:
        m &= pos_k[None, :] > (pos_b[:, None] - window)
    s = jnp.where(m[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _gqa_out(p, v)
    return out.reshape(B, 1, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Caches, written in place
# ---------------------------------------------------------------------------
#
# A layer's cache holds the sequence on axis 1: (B, S, KV, dh) keys and
# values, (B, S, lora) MLA latents and (B, S, 1, rope) MLA rope keys (one
# key for every head, held like a key cache of one KV head).  The TPU
# stores a cache whose minor axis is 64 wide with the sequence minor, and
# the score einsums read that layout as it is stored; rope keys stacked
# (L, B, S, rope) instead take a layout with the rope axis minor inside
# the layer scan, and XLA relays out the whole stack at the step's entry
# and exit.  A decode step writes its tokens with dynamic-update-slices,
# which update the buffer in place in whatever layout it has (a scatter
# has its operand relaid out with the token's features minor).


def _cache_fill(cache: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """A prompt's rows ``new`` (B, S, ...) written from position 0 of a
    layer's cache."""
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), (0,) * cache.ndim)


def _cache_write(cache: jnp.ndarray, new: jnp.ndarray, pos, layer=None) -> jnp.ndarray:
    """Write one decode step's token ``new`` (B, 1, ...) at ``pos`` (scalar,
    or (B,) for per-slot positions in continuous batching).

    ``cache`` is one layer (B, S, ...), or with ``layer`` a stage's layer
    stack (L, B, S, ...), written at that layer.  One dynamic-update-slice
    per slot, or one for a scalar position, each in place.
    """
    new = new.astype(cache.dtype)
    lead = () if layer is None else (layer,)
    rest = (0,) * (new.ndim - 2)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, new[(None,) * len(lead)], lead + (0, pos) + rest)
    for b in range(new.shape[0]):
        # index the slot's row, then add the unit axes: slicing the stacked
        # rows instead leaves XLA a layout for them that it carries back
        # into the cache as whole-stack relayouts
        row = new[b][(None,) * (len(lead) + 1)]
        cache = jax.lax.dynamic_update_slice(cache, row, lead + (b, pos[b]) + rest)
    return cache


class LayerCache:
    """One layer of a stage's decode cache, read from and written into the
    stage's stacked buffer (the layer scan's carry).

    ``view[name]`` reads the layer's array; ``view.write(name, new)``
    writes one decode step's token at the slots' positions, in place on
    the stack, and returns the layer as written; ``overwrite(state)``
    writes a recurrent block's new state over the whole layer.  ``stack``
    holds the stage's arrays as they stand.
    """

    def __init__(self, stack: Dict[str, jnp.ndarray], layer, pos):
        self.stack, self.layer, self.pos = dict(stack), layer, pos

    def __getitem__(self, name: str) -> jnp.ndarray:
        return jax.lax.dynamic_index_in_dim(self.stack[name], self.layer, keepdims=False)

    def write(self, name: str, new: jnp.ndarray) -> jnp.ndarray:
        stack = _cache_write(self.stack[name], new, self.pos, self.layer)
        self.stack[name] = shard_cache(stack, batch_axis=1)
        return self[name]

    def overwrite(self, state: Dict[str, jnp.ndarray]) -> None:
        for name, a in state.items():
            self.stack[name] = jax.lax.dynamic_update_index_in_dim(
                self.stack[name], a.astype(self.stack[name].dtype), self.layer, 0)


# ---------------------------------------------------------------------------
# Attention block (pre-norm handled by caller); returns (y, new_cache)
# ---------------------------------------------------------------------------

def attention_block(
    params,
    x: jnp.ndarray,  # (B, S, D)
    cfg: ModelConfig,
    kind: str,  # attn | attn_local | attn_global
    positions: jnp.ndarray,  # (S,) absolute positions
    cache=None,  # prefill: this layer's arrays; decode: its LayerCache
    decode_pos: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    if cfg.kv_lora_rank:
        return _mla_block(params, x, cfg, positions, cache, decode_pos)
    B, S, D = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if kind == "attn_local" else 0
    scale = cfg.attn_scale if cfg.attn_scale else dh**-0.5

    q = crossbar_linear(x, params["wq"], name="wq").reshape(B, S, H, dh)
    k = crossbar_linear(x, params["wk"], name="wk").reshape(B, S, KV, dh)
    v = crossbar_linear(x, params["wv"], name="wv").reshape(B, S, KV, dh)
    q = shard(q, "batch", None, "heads", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is None:
        out = gqa_attention(q, k, v, scale=scale, window=window, attn_cap=cfg.attn_softcap)
    elif decode_pos is None:
        # prefill: attend within the prompt and return the filled cache
        out = gqa_attention(q, k, v, scale=scale, window=window, attn_cap=cfg.attn_softcap)
        kc = shard_cache(_cache_fill(cache["k"], k))
        vc = shard_cache(_cache_fill(cache["v"], v))
        new_cache = {"k": kc, "v": vc}
    else:
        # decode: ``cache`` is a LayerCache; the token is written in place
        # on the stage's stack, and the block returns no entry
        kc = cache.write("k", k)
        vc = cache.write("v", v)
        out = decode_attention(
            q, kc, vc, decode_pos, scale=scale, window=window, attn_cap=cfg.attn_softcap
        )

    y = crossbar_linear(out.reshape(B, S, H * dh), params["wo"], name="wo")
    return shard(y, "batch", None, None), new_cache


def init_attention_cache(cfg: ModelConfig, batch: int, seq: int, dtype=jnp.bfloat16):
    if cfg.kv_lora_rank:
        return {
            "latent": jnp.zeros((batch, seq, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, seq, 1, cfg.qk_rope_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, seq, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, seq, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# MLA (deepseek-v2) — absorbed form
# ---------------------------------------------------------------------------

def mla_rope(cfg: ModelConfig):
    """(inv_freq or None, softmax scale) of MLA's decoupled rope: plain, or
    YaRN as DeepSeek-V2 defines it, whose softmax scale grows by
    ``yarn_mscale(factor, mscale_all_dim)**2``."""
    scale = (cfg.head_dim + cfg.qk_rope_dim) ** -0.5
    if not cfg.yarn_factor:
        return None, scale
    inv_freq = yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn_factor,
                             cfg.yarn_original_max_pos)
    if cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return inv_freq, scale


def _mla_block(params, x, cfg: ModelConfig, positions, cache, decode_pos):
    B, S, D = x.shape
    H, dh, rope_d, lora = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    inv_freq, scale = mla_rope(cfg)

    q = crossbar_linear(x, params["wq"], name="wq").reshape(B, S, H, dh + rope_d)
    q = shard(q, "batch", None, "heads", None)
    q_nope, q_rope = q[..., :dh], q[..., dh:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, inv_freq)

    kvd = crossbar_linear(x, params["w_kv_down"], name="w_kv_down")  # (B, S, lora + rope)
    # the latent is RMS-normed (DeepSeek-V2's kv_a_layernorm) before it is
    # used or cached, as a computation of its own on the materialized
    # latent: fused into what makes or reads it, its sum of squares may add
    # in another order, and the crossbars downstream round on the ulp
    barrier = jax.lax.optimization_barrier
    latent = barrier(rms_norm(barrier(kvd[..., :lora]), params["kv_norm"], cfg.norm_eps))
    k_rope = kvd[..., lora:]
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta, inv_freq)[..., 0, :]

    new_cache = None
    if cache is not None and decode_pos is None:
        new_cache = {"latent": shard_cache(_cache_fill(cache["latent"], latent)),
                     "k_rope": shard_cache(_cache_fill(cache["k_rope"], k_rope[:, :, None]))}
    if decode_pos is not None:
        # decode: written in place on the stage's stack (a LayerCache)
        latent_k = cache.write("latent", latent)
        rope_k = cache.write("k_rope", k_rope[:, :, None])[:, :, 0]
    else:
        # a prefill starts at position 0: its own positions are all it sees
        latent_k, rope_k = latent, k_rope
    Sk = latent_k.shape[1]

    # Absorb W_uk into the query, one crossbar projection per head:
    # q_abs (B, S, H, lora)
    q_abs = grouped_linear(q_nope.transpose(2, 0, 1, 3), params["w_uk"], "w_uk")
    q_abs = q_abs.transpose(1, 2, 0, 3)

    pos_k = jnp.arange(Sk)

    def block(q_abs_blk, q_rope_blk, start, single_pos=None):
        # operands in the cache's dtype, products at its full precision (the
        # TPU's default rounds float32 operands to bfloat16), summed in f32
        s = jnp.einsum(
            "bqhl,bsl->bqhs", q_abs_blk.astype(latent_k.dtype), latent_k,
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )
        s = s + jnp.einsum(
            "bqhr,bsr->bqhs", q_rope_blk.astype(rope_k.dtype), rope_k,
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )
        s = s * scale
        if single_pos is None:
            pos_q = start + jnp.arange(q_abs_blk.shape[1])
            m = pos_k[None, :] <= pos_q[:, None]
            s = jnp.where(m[None, :, None, :], s, NEG_INF)
        else:
            pos_b = jnp.broadcast_to(jnp.asarray(single_pos), (B,))
            m = pos_k[None, :] <= pos_b[:, None]
            s = jnp.where(m[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # attend over the latent; the per-head up-projection follows
        return jnp.einsum(
            "bqhs,bsl->bqhl", p.astype(latent_k.dtype), latent_k,
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )

    if decode_pos is not None:
        out = block(q_abs, q_rope, 0, single_pos=decode_pos)
    elif S <= Q_CHUNK:
        out = block(q_abs, q_rope, 0)
    else:
        nc = S // Q_CHUNK
        assert S % Q_CHUNK == 0
        qa = q_abs.reshape(B, nc, Q_CHUNK, H, lora).transpose(1, 0, 2, 3, 4)
        qr = q_rope.reshape(B, nc, Q_CHUNK, H, rope_d).transpose(1, 0, 2, 3, 4)

        def body(_, inp):
            qa_b, qr_b, idx = inp
            return None, jax.checkpoint(block)(qa_b, qr_b, idx * Q_CHUNK)

        _, outs = jax.lax.scan(body, None, (qa, qr, jnp.arange(nc)))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, lora)

    # W_uv, one crossbar projection per head over every position: (B, S, H, dh)
    out = grouped_linear(out.transpose(2, 0, 1, 3).astype(x.dtype), params["w_uv"], "w_uv")
    out = out.transpose(1, 2, 0, 3)
    y = crossbar_linear(out.reshape(B, S, H * dh), params["wo"], name="wo")
    return shard(y, "batch", None, None), new_cache
