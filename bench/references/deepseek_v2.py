"""Plain reference of DeepSeek-V2 (Lite) at one chip's share of an
expert-parallel deployment: multi-head latent attention and routed plus
shared experts, with every weight projection on the crossbar's fixed-point
datapath, in straightforward ``jax.numpy`` and float32.

It imports nothing of the program under test.  The datapath (offset-encoded
16-bit input codes, 16-bit weight codes, the exact product and its 16-bit
output window) is ``references/llama.py``'s ``crossbar_linear``, with each
input row offset and scaled by its own range where the configuration says
so (``linear``); what this module states is the model the program serves,
after the published description (arXiv:2405.04434 and the checkpoint's
``modeling_deepseek.py``):

* attention (MLA, no query compression): ``q = x Wq`` splits per head into
  128 "nope" and 64 rope dimensions; ``x W_kv_down`` gives a 512-wide latent,
  RMS-normed with a gain, and one 64-wide rope key shared by the heads.  The
  keys and values are the latent's per-head up-projections ``W_uk``,
  ``W_uv``, computed absorbed: each head's query goes through its ``W_uk``
  (128 -> 512) onto the latent, the scores are ``q_abs . c + q_rope .
  k_rope`` times ``192**-0.5 * yarn_mscale(40, 0.707)**2``, the softmax
  mixes latents, and each head's mix goes through its ``W_uv`` (512 -> 128)
  before ``Wo``.  Every per-head up-projection is a crossbar projection of
  its own, as the chip holds it;
* rope is YaRN over the rope dimensions (factor 40 over 4,096 positions,
  ``beta_fast`` 32, ``beta_slow`` 1), rotating halves;
* experts: a softmax router over all published experts picks the top 6; a
  token's output is the sum over the chosen experts this chip holds of the
  raw probability (times ``routed_scaling_factor``, with
  ``norm_topk_prob`` false) times that expert's SwiGLU, plus the shared
  experts' SwiGLU, once.  Each held expert's output counts for exactly the
  tokens routed to it (a call's input range, where the configuration takes
  one, covers those rows alone).  The absent experts' part is left out, as
  the chip leaves it out;
* the first layer's FFN is a dense SwiGLU; the head is untied, over the
  vocabulary slice the configuration holds.

Departures from the checkpoint's code, by design: rope rotates halves where
the checkpoint de-interleaves ``q_pe``/``k_pe`` first (a fixed permutation
of random weight columns); norm gains are stored as offsets from 1.

Attention runs in query blocks, so that a forward over 8,192 positions
fits.  ``bits=None`` gives the float32 digital forward.

It also counts the work of one decode step of the served program
(``decode_kernels``, ``decode_model_flops``).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import harness

_llama = harness.load_reference("llama")
Bits = _llama.Bits
rms_norm = _llama.rms_norm

Q_BLOCK = 512  # query rows per attention block
barrier = jax.lax.optimization_barrier


class Dims(NamedTuple):
    """The sizes of the model, from a configuration file: ``n_experts`` is
    the router's width (the published count), ``held`` the experts here."""

    n_layers: int
    n_dense: int
    d_model: int
    n_heads: int
    nope: int
    rope: int
    v_dim: int
    kv_lora: int
    d_ff: int
    expert_ff: int
    n_experts: int
    held: int
    top_k: int
    n_shared: int
    vocab: int
    rope_theta: float
    norm_eps: float
    norm_topk: bool
    routed_scale: float
    # factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim
    yarn: Tuple[float, float, float, float, float, float]
    # each input row quantized over its own range (the program block's
    # ``crossbar_row_ranges``), else over the call's
    row_ranges: bool

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        y = cfg["rope_scaling"]
        return cls(
            n_layers=cfg["num_hidden_layers"],
            n_dense=cfg["first_k_dense_replace"],
            d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"],
            v_dim=cfg["v_head_dim"],
            kv_lora=cfg["kv_lora_rank"],
            d_ff=cfg["intermediate_size"],
            expert_ff=cfg["moe_intermediate_size"],
            n_experts=cfg["published"]["n_routed_experts"],
            held=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            n_shared=cfg["n_shared_experts"],
            vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            routed_scale=float(cfg["routed_scaling_factor"]),
            yarn=(float(y["factor"]), float(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
                  float(y["mscale_all_dim"])),
            row_ranges=bool(cfg.get("program", {}).get("crossbar_row_ranges", False)),
        )


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def _layer_shapes(d: Dims, n: int, moe: bool) -> Dict:
    D, H = d.d_model, d.n_heads
    mixer = {
        "wq": (n, D, H * (d.nope + d.rope)),
        "w_kv_down": (n, D, d.kv_lora + d.rope),
        "kv_norm": (n, d.kv_lora),
        "w_uk": (n, H, d.nope, d.kv_lora),
        "w_uv": (n, H, d.kv_lora, d.v_dim),
        "wo": (n, H * d.v_dim, D),
    }
    if moe:
        F, Fs = d.expert_ff, d.expert_ff * d.n_shared
        ffn = {"router": (n, D, d.n_experts),
               "wi": (n, d.held, D, F), "wg": (n, d.held, D, F), "wo": (n, d.held, F, D),
               "shared_wi": (n, D, Fs), "shared_wg": (n, D, Fs), "shared_wo": (n, Fs, D)}
    else:
        ffn = {"wi": (n, D, 2 * d.d_ff), "wo": (n, d.d_ff, D)}
    return {"b0": {"norm1": (n, D), "mixer": mixer, "norm2": (n, D), "ffn": ffn}}


def param_shapes(d: Dims) -> Dict:
    """The weights' tree as the served program takes it: the dense layers
    in ``stage0``, the expert layers in ``stage1``, each stacked on a
    leading axis; norms as offsets from 1; the dense SwiGLU's up and gate
    halves side by side in ``wi``; ``W_uk``/``W_uv`` head-major."""
    return {
        "embed": {"tokens": (d.vocab, d.d_model)},
        "stage0": _layer_shapes(d, d.n_dense, False),
        "stage1": _layer_shapes(d, d.n_layers - d.n_dense, True),
        "final_norm": (d.d_model,),
        "head": (d.d_model, d.vocab),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def init_params(key, d: Dims):
    """Seeded float32 weights, in one program on the device: normal with
    standard deviation fan_in**-0.5 for projections, 0.02 for the
    embedding, and random offsets of 0.1 for the norms' gains (so that a
    mistake in how a gain is applied shows)."""
    shapes = param_shapes(d)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = str(path[-1].key)
        if "norm" in name:
            std = 0.1
        elif name == "tokens":
            std = 0.02
        else:
            std = shape[-2] ** -0.5
        out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# The crossbar datapath
# ---------------------------------------------------------------------------


class Weight(NamedTuple):
    """A crossbar weight and its column sums, the constant of the offset
    correction (``with_column_sums``)."""

    w: jnp.ndarray
    colsum: jnp.ndarray


def column_sums(w: jnp.ndarray) -> jnp.ndarray:
    """The column sums of each (K, N) slab of ``w`` (..., K, N), each slab
    summed on its own, as the chip sums it once when it is programmed.  A
    sum of thousands of float32 terms depends on its order, and the offset
    correction cancels most of a row's product, so an ulp here is an ulp of
    the output: the barriers keep each sum a reduction of its own slab
    alone, which XLA does not fuse into the forward around it."""
    slabs = w.reshape((-1,) + w.shape[-2:])
    sums = [barrier(jnp.sum(barrier(slabs[i]), axis=0)) for i in range(slabs.shape[0])]
    return jnp.stack(sums).reshape(w.shape[:-2] + w.shape[-1:])


# the weights that run on the crossbar, by their name in the tree
CROSSBAR = ("wq", "w_kv_down", "w_uk", "w_uv", "wo", "wi", "wg", "router",
            "shared_wi", "shared_wg", "shared_wo", "head")


def with_column_sums(params: Dict) -> Dict:
    """``params`` with every crossbar weight a ``Weight`` that carries its
    column sums."""
    def one(path, leaf):
        return Weight(leaf, column_sums(leaf)) if str(path[-1].key) in CROSSBAR else leaf
    return jax.tree_util.tree_map_with_path(one, params)


def linear(x, w, d: Dims, bits: Optional[Bits], valid=None):
    """``x @ w`` through the datapath (``bits``), or digitally (None); ``w``
    an array or a ``Weight``.  With ``d.row_ranges`` each row of ``x`` is
    offset and scaled by its own range (``shift = min(row)``, ``scale =
    max(row - shift) / (2**input_bits - 1)``), so the other rows and
    ``valid`` do not touch it; otherwise the range is the call's, over the
    rows ``valid`` marks (``references/llama.py``).  The codes, their exact
    product, the output window and the offset correction are llama.py's."""
    w, colsum = (w.w, w.colsum) if isinstance(w, Weight) else (w, None)
    if bits is None or not d.row_ranges:
        return _llama.crossbar_linear(x, w, bits, valid)
    if colsum is None:
        colsum = column_sums(w)
    lead, K = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, K).astype(jnp.float32)
    shift = jnp.min(x2, axis=1, keepdims=True)
    xs = barrier(x2 - shift)
    x_scale = barrier(jnp.maximum(jnp.max(xs, axis=1, keepdims=True), 1e-9)
                      / ((1 << bits.input_bits) - 1))
    xq = jnp.clip(jnp.round(xs / x_scale), 0, (1 << bits.input_bits) - 1).astype(jnp.int32)
    wmax = (1 << (bits.weight_bits - 1)) - 1
    w_scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9) / wmax
    wq = jnp.clip(jnp.round(w / w_scale), -wmax - 1, wmax).astype(jnp.int32)
    up_x, up_w = 16 - bits.input_bits, 16 - bits.weight_bits
    drop = _llama.drop_bits(K, bits)
    yq = _llama.exact_codes_product(xq << up_x, wq << up_w, drop + up_x + up_w, bits.out_bits)
    y = yq.astype(jnp.float32) * (barrier(x_scale * w_scale) * 2.0 ** drop)
    y, corr = barrier((y, shift * colsum))
    return (y + corr).reshape(lead + (w.shape[1],))


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


def yarn_get_mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(d: Dims) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` frequencies."""
    factor, original, beta_fast, beta_slow, _, _ = d.yarn
    dim, base = d.rope, d.rope_theta

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    return (inter * (1.0 - mask) + extra * mask).astype(np.float32)


def softmax_scale(d: Dims) -> float:
    factor, _, _, _, _, mscale_all_dim = d.yarn
    return (d.nope + d.rope) ** -0.5 * yarn_get_mscale(factor, mscale_all_dim) ** 2


def rope(x, positions, d: Dims):
    """YaRN rotary embedding, halves rotated: x (..., S, H, r), positions
    (S,)."""
    factor, _, _, _, mscale, mscale_all_dim = d.yarn
    m = yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor, mscale_all_dim)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(d))
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def latent_norm(c, gain, d: Dims):
    """The latent's RMS norm, a computation of its own on the materialized
    latent (the barriers): fused into what makes or reads it, its sum of
    squares may add in another order."""
    return barrier(rms_norm(barrier(c), gain, d.norm_eps))


def per_head(x, w, d: Dims, bits, valid):
    """Each head's own crossbar projection: x (B, S, H, K), w (H, K, N)."""
    def one(_, xs):
        return None, linear(xs[0], xs[1], d, bits, valid)

    _, y = jax.lax.scan(one, None, (jnp.moveaxis(x, 2, 0), w))
    return jnp.moveaxis(y, 0, 2)


def attend(q_abs, q_pe, c, k_pe, d: Dims, dtype=None):
    """Causal absorbed attention in query blocks: q_abs (B, S, H, lora),
    q_pe (B, S, H, r) against the latents c (B, S, lora) and rope keys
    k_pe (B, S, r); returns each head's latent mix (B, S, H, lora).  With
    ``dtype`` (the control's bfloat16) the latents, queries and softmax
    weights are held in it, and the products sum in float32."""
    B, S, H, L = q_abs.shape
    if dtype is not None:
        q_abs, q_pe, c, k_pe = (a.astype(dtype) for a in (q_abs, q_pe, c, k_pe))
    qb = math.gcd(S, Q_BLOCK)
    pos_k = jnp.arange(S)
    scale = softmax_scale(d)

    def block(_, i):
        qa = jax.lax.dynamic_slice_in_dim(q_abs, i * qb, qb, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_pe, i * qb, qb, axis=1)
        s = (jnp.einsum("bqhl,bsl->bhqs", qa, c, preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bsr->bhqs", qr, k_pe, preferred_element_type=jnp.float32))
        pos_q = i * qb + jnp.arange(qb)
        s = jnp.where((pos_k[None, :] <= pos_q[:, None])[None, None], s * scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(c.dtype)
        return None, jnp.einsum("bhqs,bsl->bqhl", p, c, preferred_element_type=jnp.float32)

    _, out = jax.lax.scan(block, None, jnp.arange(S // qb))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, L)


def mla(p, h, positions, valid, d: Dims, bits, attn_dtype=None):
    B, S, _ = h.shape
    H = d.n_heads
    q = linear(h, p["wq"], d, bits, valid).reshape(B, S, H, d.nope + d.rope)
    q_nope, q_pe = q[..., :d.nope], rope(q[..., d.nope:], positions, d)
    kv = linear(h, p["w_kv_down"], d, bits, valid)
    c = latent_norm(kv[..., :d.kv_lora], p["kv_norm"], d)
    k_pe = rope(kv[..., None, d.kv_lora:], positions, d)[..., 0, :]
    q_abs = per_head(q_nope, p["w_uk"], d, bits, valid)
    mix = attend(q_abs, q_pe, c, k_pe, d, attn_dtype)
    o = per_head(mix, p["w_uv"], d, bits, valid).reshape(B, S, H * d.v_dim)
    return linear(o, p["wo"], d, bits, valid)


def swiglu(h, wi, wg, wo, d: Dims, bits, valid):
    up = linear(h, wi, d, bits, valid)
    gate = linear(h, wg, d, bits, valid)
    return linear(up * jax.nn.silu(gate), wo, d, bits, valid)


def experts(p, h, valid, d: Dims, bits):
    """The held experts' part of the routed output, plus the shared
    experts."""
    B, S, _ = h.shape
    probs = jax.nn.softmax(linear(h, p["router"], d, bits, valid), axis=-1)
    top, idx = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    else:
        top = top * d.routed_scale
    rows = jnp.ones((B, S), bool) if valid is None else valid

    def one(y, xs):
        e, wi, wg, wo = xs
        chosen = idx == e  # (B, S, k): at most one true per token
        routed = jnp.any(chosen, axis=-1) & rows
        gate = jnp.sum(jnp.where(chosen, top, 0.0), axis=-1)
        out = swiglu(h, wi, wg, wo, d, bits, routed)
        return y + jnp.where(routed[..., None], gate[..., None] * out, 0.0), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (jnp.arange(d.held), p["wi"], p["wg"], p["wo"]))
    return y + swiglu(h, p["shared_wi"], p["shared_wg"], p["shared_wo"], d, bits, valid)


def layer(p, x, positions, valid, d: Dims, bits, moe: bool, attn_dtype=None):
    """One decoder layer on x (B, S, D), causal within the sequence."""
    x = x + mla(p["mixer"], rms_norm(x, p["norm1"], d.norm_eps), positions, valid, d, bits,
                attn_dtype)
    h = rms_norm(x, p["norm2"], d.norm_eps)
    f = p["ffn"]
    if moe:
        return x + experts(f, h, valid, d, bits)
    up, gate = jnp.split(linear(h, f["wi"], d, bits, valid), 2, axis=-1)
    return x + linear(up * jax.nn.silu(gate), f["wo"], d, bits, valid)


def forward(params, tokens, d: Dims, bits: Optional[Bits], n_valid=None, attn_dtype=None):
    """Full causal forward of tokens (B, S); logits at every position.
    With ``n_valid``, positions from ``n_valid`` on are padding: the
    datapath's input ranges leave them out, and causality keeps them from
    the positions before.  ``attn_dtype`` lowers the attention's precision
    (see ``attend``)."""
    B, S = tokens.shape
    pos = jnp.arange(S)
    valid = None if n_valid is None else jnp.broadcast_to(pos[None, :] < n_valid, (B, S))
    params = with_column_sums(params)
    x = params["embed"]["tokens"][tokens]
    for stage, moe in (("stage0", False), ("stage1", True)):
        def body(x, p, moe=moe):
            return layer(p, x, pos, valid, d, bits, moe, attn_dtype), None

        x, _ = jax.lax.scan(body, x, params[stage]["b0"])
    h = rms_norm(x, params["final_norm"], d.norm_eps)
    return linear(h, params["head"], d, bits, valid)


# ---------------------------------------------------------------------------
# Work of one decode step, counted from shapes
# ---------------------------------------------------------------------------


def _capacity(rows: int) -> int:
    """The rows of each held expert's buffer: the program's dropless
    capacity, the token count rounded up to 8."""
    return max(8, -(-rows // 8) * 8)


def decode_kernels(dims: Dims, rows: int) -> List[Tuple[str, int, int, int]]:
    """(name, M, K, N), one per crossbar kernel call of one decode step over
    a pool of ``rows`` slots: the attention projections and the dense and
    shared FFNs take the whole pool, ``W_uk``/``W_uv`` once per head, and
    each held expert's three projections its buffer of ``_capacity(rows)``."""
    D, H, L = dims.d_model, dims.n_heads, dims.kv_lora
    F, Fe, Fs, cap = dims.d_ff, dims.expert_ff, dims.expert_ff * dims.n_shared, _capacity(rows)
    calls = []
    for i in range(dims.n_layers):
        calls += [("wq", rows, D, H * (dims.nope + dims.rope)),
                  ("w_kv_down", rows, D, L + dims.rope)]
        calls += [("w_uk", rows, dims.nope, L)] * H + [("w_uv", rows, L, dims.v_dim)] * H
        calls.append(("wo", rows, H * dims.v_dim, D))
        if i < dims.n_dense:
            calls += [("wi", rows, D, 2 * F), ("ffn_wo", rows, F, D)]
        else:
            calls.append(("router", rows, D, dims.n_experts))
            calls += [("expert_wi", cap, D, Fe), ("expert_wg", cap, D, Fe),
                      ("expert_wo", cap, Fe, D)] * dims.held
            calls += [("shared_wi", rows, D, Fs), ("shared_wg", rows, D, Fs),
                      ("shared_wo", rows, Fs, D)]
    return calls + [("head", rows, D, dims.vocab)]


def decode_model_flops(dims: Dims, contexts: Sequence[int]) -> float:
    """Model operations of one decode step whose active rows attend over
    ``contexts`` positions each: 2 per weight a row passes through, where
    the held experts see ``top_k * held / n_experts`` of each row's
    assignments (routing spread evenly), and per layer, head and position
    attended the absorbed attention's scores over the latent and rope key
    and its mix of latents (``2 * (lora + rope) + 2 * lora``)."""
    rows = len(contexts)
    D, H, L, Fe = dims.d_model, dims.n_heads, dims.kv_lora, dims.expert_ff
    n_moe = dims.n_layers - dims.n_dense
    attn = (D * H * (dims.nope + dims.rope) + D * (L + dims.rope)
            + H * (dims.nope * L + L * dims.v_dim) + H * dims.v_dim * D)
    dense = 3 * D * dims.d_ff
    shared = D * dims.n_experts + 3 * D * Fe * dims.n_shared
    routed = 3 * D * Fe * dims.top_k * dims.held / dims.n_experts
    per_row = (dims.n_layers * attn + dims.n_dense * dense + n_moe * (shared + routed)
               + D * dims.vocab)
    per_position = dims.n_layers * H * (4 * L + 2 * dims.rope)
    return 2.0 * per_row * rows + per_position * float(sum(contexts))
