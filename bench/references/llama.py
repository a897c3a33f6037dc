"""Plain reference of the served model: a Llama-architecture decoder
(SmolLM) whose every weight projection runs the crossbar's fixed-point
datapath, in straightforward ``jax.numpy`` and float32.

It imports nothing of the program under test.  What it states is the
semantics the program serves:

* a projection ``y = x @ w`` offset-encodes its input (``shift = min(x)``
  over the whole call), quantizes the shifted input to ``input_bits``
  unsigned codes with one scale ``max(x - shift) / (2**input_bits - 1)``
  per call, and the weight to ``weight_bits`` signed codes with one scale
  ``max|w| / (2**(weight_bits - 1) - 1)`` per matrix;
* the product of the codes is exact, and the output keeps the bits that a
  ``out_bits`` window holds after dropping
  ``drop = input_bits + weight_bits - 1 + ceil(log2 K) - (out_bits - 1)``
  low bits (round half up, then clamp);
* the result is scaled back and the offset corrected with the float
  weights' column sums.

The exact product is built from 8-bit limbs: each limb product is exact in
a bfloat16 matrix product with float32 accumulation over chunks of 256
rows, and the chunk sums add exactly in int32.  ``bits=None`` gives the
float32 digital forward (no crossbar).

Everything else (RMSNorm, rotary embedding, grouped-query attention,
SwiGLU, the tied head) follows the published Llama description in float32
with ``jax.default_matmul_precision("highest")``.

It also counts the work of one decode step of the served program
(``decode_kernels``, ``decode_model_flops``), which the per-layer readers
of the kernels' roofline and the step's MFU take from here.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 256  # rows per exact limb product: 255 * 255 * 256 < 2**24


class Dims(NamedTuple):
    """The sizes of the model, from a configuration file."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @classmethod
    def from_config(cls, cfg: Dict) -> "Dims":
        return cls(
            n_layers=cfg["num_hidden_layers"],
            d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"]),
            d_ff=cfg["intermediate_size"],
            vocab=cfg["vocab_size"],
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
        )


class Bits(NamedTuple):
    """Widths of the crossbar datapath."""

    input_bits: int = 16
    weight_bits: int = 16
    out_bits: int = 16


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def param_shapes(d: Dims) -> Dict:
    """The weights' tree as the served program takes it: layers stacked on a
    leading axis, the norms as offsets from 1, SwiGLU's up and gate halves
    side by side in ``wi``, the embedding doubling as the head."""
    L, D, F = d.n_layers, d.d_model, d.d_ff
    q, kv = d.n_heads * d.head_dim, d.n_kv_heads * d.head_dim
    return {
        "embed": {"tokens": (d.vocab, D)},
        "stage0": {"b0": {
            "norm1": (L, D),
            "mixer": {"wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv), "wo": (L, q, D)},
            "norm2": (L, D),
            "ffn": {"wi": (L, D, 2 * F), "wo": (L, F, D)},
        }},
        "final_norm": (D,),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def init_params(key, d: Dims):
    """Seeded float32 weights, in one program on the device: normal with
    standard deviation fan_in**-0.5 for projections, 0.02 for the
    embedding, and small random offsets for the norms' gains (so that a
    mistake in how a gain is applied shows)."""
    shapes = param_shapes(d)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shape in zip(keys, leaves):
        if len(shape) <= 2 and shape[-1] == d.d_model and shape != (d.vocab, d.d_model):
            std = 0.1  # a norm gain's offset from 1
        elif shape == (d.vocab, d.d_model):
            std = 0.02
        else:
            std = shape[-2] ** -0.5
        out.append(jax.random.normal(k, shape, jnp.float32) * std)
    return jax.tree.unflatten(tree, out)


# ---------------------------------------------------------------------------
# The crossbar datapath
# ---------------------------------------------------------------------------


def drop_bits(k: int, bits: Bits) -> int:
    """Low bits the output scaling drops for a K-row dot product, so that
    the worst case fits the ``out_bits`` window."""
    return bits.input_bits + bits.weight_bits - 1 + math.ceil(math.log2(max(2, k))) - (bits.out_bits - 1)


def _limb_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Exact int32 ``a @ b`` for |a|, |b| < 256 and K a multiple of CHUNK."""
    M, K = a.shape
    acc = jnp.zeros((M, b.shape[1]), jnp.int32)
    for c in range(0, K, CHUNK):
        part = jnp.dot(
            a[:, c:c + CHUNK].astype(jnp.bfloat16), b[c:c + CHUNK].astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        acc = acc + part.astype(jnp.int32)
    return acc


def exact_codes_product(xq: jnp.ndarray, wq: jnp.ndarray, drop: int, out_bits: int) -> jnp.ndarray:
    """``clip(floor((xq @ wq + 2**(drop-1)) / 2**drop))`` exactly, for
    unsigned ``xq`` < 2**16 and signed ``wq`` in [-2**15, 2**15)."""
    assert drop >= 17, drop
    K = xq.shape[1]
    pad = -K % CHUNK
    if pad:
        xq = jnp.pad(xq, ((0, 0), (0, pad)))
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    xh, xl = xq >> 8, xq & 255
    wh, wl = wq >> 8, wq & 255  # wh signed in [-128, 128)
    hh = _limb_dot(xh, wh)
    mid = _limb_dot(xh, wl) + _limb_dot(xl, wh)
    ll = _limb_dot(xl, wl)
    # S = hh * 2**16 + mid * 2**8 + ll = H * 2**16 + L, 0 <= L < 2**16
    H = hh + (mid >> 8) + (ll >> 16)
    L = ((mid & 255) << 8) + (ll & 0xFFFF)
    H = H + (L >> 16)
    # floor((H * 2**16 + L + 2**(drop-1)) / 2**drop), with 0 <= L < 2**16
    y = (H + (1 << (drop - 17))) >> (drop - 16)
    lim = 1 << (out_bits - 1)
    return jnp.clip(y, -lim, lim - 1)


def crossbar_linear(x: jnp.ndarray, w: jnp.ndarray, bits: Optional[Bits],
                    valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``x @ w`` through the datapath (``bits``), or digitally (None).  The
    input's range is taken over every row of ``x``, or over the rows that
    ``valid`` (one flag per row, leading dimensions flattened) marks."""
    if bits is None:
        return jnp.einsum("...k,kn->...n", x, w)
    lead, K = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, K).astype(jnp.float32)
    rows = jnp.ones((x2.shape[0], 1), bool) if valid is None else valid.reshape(-1, 1)
    shift = jnp.min(jnp.where(rows, x2, jnp.inf))
    xs = x2 - shift
    x_scale = jnp.maximum(jnp.max(jnp.where(rows, xs, 0.0)), 1e-9) / ((1 << bits.input_bits) - 1)
    xq = jnp.clip(jnp.round(xs / x_scale), 0, (1 << bits.input_bits) - 1).astype(jnp.int32)
    wmax = (1 << (bits.weight_bits - 1)) - 1
    w_scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9) / wmax
    wq = jnp.clip(jnp.round(w / w_scale), -wmax - 1, wmax).astype(jnp.int32)
    # narrower codes ride the same 16-bit limb product, shifted up
    up_x, up_w = 16 - bits.input_bits, 16 - bits.weight_bits
    drop = drop_bits(K, bits)
    yq = exact_codes_product(xq << up_x, wq << up_w, drop + up_x + up_w, bits.out_bits)
    y = yq.astype(jnp.float32) * (x_scale * w_scale * 2.0 ** drop)
    y = y + shift * jnp.sum(w, axis=0)
    return y.reshape(lead + (w.shape[1],))


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain)


def rope(x, positions, theta):
    """Rotary embedding, halves rotated (Llama): x (..., S, H, dh),
    positions broadcastable to (..., S)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attend(q, k, v, mask, d: Dims, dtype=None):
    """q (B, S, H, dh) against k, v (B, T, KV, dh); mask (B, S, T).  With
    ``dtype`` (the control's bfloat16) the cache, the queries and the
    softmax's weights are held in it, and the products sum in float32."""
    rep = d.n_heads // d.n_kv_heads
    if dtype is not None:
        q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32) * d.head_dim ** -0.5
    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, v, preferred_element_type=jnp.float32)


def layer(p, x, positions, valid, d: Dims, bits, attn_dtype=None):
    """One decoder layer on x (B, S, D), causal within the sequence."""
    B, S, _ = x.shape
    lin = functools.partial(crossbar_linear, bits=bits, valid=valid)
    h = rms_norm(x, p["norm1"], d.norm_eps)
    q = lin(h, p["mixer"]["wq"]).reshape(B, S, d.n_heads, d.head_dim)
    k = lin(h, p["mixer"]["wk"]).reshape(B, S, d.n_kv_heads, d.head_dim)
    v = lin(h, p["mixer"]["wv"]).reshape(B, S, d.n_kv_heads, d.head_dim)
    q, k = rope(q, positions, d.rope_theta), rope(k, positions, d.rope_theta)
    mask = jnp.broadcast_to(positions[:, None] >= positions[None, :], (B, S, S))
    o = attend(q, k, v, mask, d, attn_dtype).reshape(B, S, -1)
    x = x + lin(o, p["mixer"]["wo"])
    h = rms_norm(x, p["norm2"], d.norm_eps)
    up, gate = jnp.split(lin(h, p["ffn"]["wi"]), 2, axis=-1)
    return x + lin(up * jax.nn.silu(gate), p["ffn"]["wo"])


def forward(params, tokens, d: Dims, bits: Optional[Bits], n_valid=None, attn_dtype=None):
    """Full causal forward of tokens (B, S); logits at every position.
    With ``n_valid``, positions from ``n_valid`` on are padding: the
    datapath's input ranges leave them out, and causality keeps them from
    the positions before.  ``attn_dtype`` lowers the attention's precision
    (see ``attend``)."""
    B, S = tokens.shape
    pos = jnp.arange(S)
    valid = None if n_valid is None else jnp.broadcast_to(pos[None, :] < n_valid, (B, S))
    x = params["embed"]["tokens"][tokens]

    def body(x, p):
        return layer(p, x, pos, valid, d, bits, attn_dtype), None

    x, _ = jax.lax.scan(body, x, params["stage0"]["b0"])
    h = rms_norm(x, params["final_norm"], d.norm_eps)
    return crossbar_linear(h, params["embed"]["tokens"].T, bits, valid)


def np_codes_product(xq: np.ndarray, wq: np.ndarray, drop: int, out_bits: int) -> np.ndarray:
    """``exact_codes_product`` in float64 numpy, exact while the sum stays
    under 2**53: the check of the limb arithmetic."""
    s = xq.astype(np.float64) @ wq.astype(np.float64)
    y = np.floor((s + 2.0 ** (drop - 1)) / 2.0 ** drop)
    lim = 1 << (out_bits - 1)
    return np.clip(y, -lim, lim - 1).astype(np.int64)


# ---------------------------------------------------------------------------
# Work of one decode step, counted from shapes
# ---------------------------------------------------------------------------


def projections(dims) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every programmed projection one token passes
    through, the layers' repeated ``n_layers`` times, then the head."""
    D, F = dims.d_model, dims.d_ff
    q, kv = dims.n_heads * dims.head_dim, dims.n_kv_heads * dims.head_dim
    layer = [("wq", D, q), ("wk", D, kv), ("wv", D, kv), ("wo", q, D),
             ("wi", D, 2 * F), ("ffn_wo", F, D)]
    return layer * dims.n_layers + [("head", D, dims.vocab)]


def programmed_weights(dims) -> int:
    return sum(k * n for _, k, n in projections(dims))


def decode_kernels(dims, rows: int) -> List[Tuple[str, int, int, int]]:
    """(name, M, K, N), one per crossbar kernel call of one decode step over
    a pool of ``rows`` slots: every projection takes the whole pool."""
    return [(name, rows, k, n) for name, k, n in projections(dims)]


def decode_model_flops(dims, contexts: Sequence[int]) -> float:
    """Model operations of one decode step whose active rows attend over
    ``contexts`` positions each: 2 per programmed weight per row, and
    4 * heads * head_dim per layer per position attended (scores and
    values)."""
    attn = 4.0 * dims.n_heads * dims.head_dim * dims.n_layers
    return 2.0 * programmed_weights(dims) * len(contexts) + attn * float(sum(contexts))
