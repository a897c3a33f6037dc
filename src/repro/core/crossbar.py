"""Bit-exact functional model of the ISAAC/Newton analog crossbar datapath.

The modeled pipeline (paper §II.C / §III):

* a ``rows x cols`` memristor crossbar holds one ``cell_bits``-bit slice of
  each weight; a 16-bit weight spans ``n_slices`` crossbars,
* a 16-bit input is streamed ``dac_bits`` (=1) bit per 100 ns iteration,
* per (iteration ``t``, slice ``s``, row-group ``g``) each bitline produces a
  <= 9-bit partial dot product which an ADC digitizes,
* shift-and-add over slices and iterations builds the exact 39-bit (for one
  128-row group) accumulator; groups are summed digitally,
* the scaling stage drops ``drop_lsb`` LSBs (round-half-up, after Gupta et
  al. [11]) and clamps to ``out_bits`` — the paper's "10 LSBs dropped, 13 MSBs
  clamp" for the 16b x 16b, 128-row case.

Everything is implemented in int32 two-limb arithmetic (radix 2**20) so the
model is bit-exact under JAX's default 32-bit integers and maps directly onto
the Pallas kernel's accumulation strategy.

Signed weights are stored **biased** (cell codes ``w + 2**15``), and the bias
``2**15 * sum(x)`` is removed digitally after accumulation — this is how
ISAAC/Newton handle signedness with non-negative conductances.

The adaptive-ADC machinery (paper §III.A.3, Fig 5) lives in ``adc.py``; this
module exposes the hooks it needs (per-(t, s) partial quantization + overflow
flags) and the conversion statistics that drive the energy model.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fixedpoint as fxp

RADIX_BITS = 20
RADIX = 1 << RADIX_BITS
RADIX_MASK = RADIX - 1


@dataclasses.dataclass(frozen=True)
class CrossbarSpec:
    """Static description of one crossbar datapath (paper Table I defaults)."""

    rows: int = 128  # wordlines simultaneously active
    cols: int = 128  # bitlines per crossbar
    cell_bits: int = 2
    dac_bits: int = 1
    weight_bits: int = 16
    input_bits: int = 16
    out_bits: int = 16
    drop_lsb: int = 10  # LSBs dropped by the output scaling stage
    signed_weights: bool = True
    # input range (offset and scale of the input codes) taken per row, a
    # per-vector DAC range, instead of over the whole call: a row's output
    # then depends on that row alone (the programmed path,
    # ``device.programmed.programmed_linear``, reads it)
    row_ranges: bool = False

    @property
    def n_slices(self) -> int:
        return -(-self.weight_bits // self.cell_bits)

    @property
    def n_iters(self) -> int:
        return -(-self.input_bits // self.dac_bits)

    @property
    def partial_max(self) -> int:
        """Max value of one column partial: rows * (2^cell-1) * (2^dac-1)."""
        return self.rows * ((1 << self.cell_bits) - 1) * ((1 << self.dac_bits) - 1)

    @property
    def adc_bits(self) -> int:
        """Bits needed to represent one lossless column conversion (9 for default)."""
        return max(1, math.ceil(math.log2(self.partial_max + 1)))

    @property
    def acc_bits(self) -> int:
        """Exact accumulator width for a single row-group (39 for default)."""
        total_max = self.partial_max * sum(
            1 << self.base_shift(t, s)
            for t in range(self.n_iters)
            for s in range(self.n_slices)
        )
        return max(1, math.ceil(math.log2(total_max + 1)))

    @property
    def weight_bias(self) -> int:
        return (1 << (self.weight_bits - 1)) if self.signed_weights else 0

    def base_shift(self, t: int, s: int) -> int:
        """Accumulator bit position of partial (iteration t, slice s)."""
        return t * self.dac_bits + s * self.cell_bits

    def replace(self, **kw) -> "CrossbarSpec":
        return dataclasses.replace(self, **kw)


DEFAULT_SPEC = CrossbarSpec()


@dataclasses.dataclass
class ConversionStats:
    """ADC work accounting — the paper's currency for energy.

    ``conversions``: number of ADC samples actually taken (one per column x
    group x t x s x input-vector, minus any skipped).  ``bit_decisions``:
    total SAR bit tests performed, which is what the adaptive scheme
    reduces.  ``skipped_conversions``: samples a zero-plane-aware ADC never
    takes because the input bit-plane for the whole row block is zero
    (kernel ``skip_zero_planes`` / Ibrayev et al. activity skipping);
    ``conversions + skipped_conversions`` is the dense count.
    ``iterations``: 100 ns crossbar cycles consumed.  All python ints.

    ``a + b`` models *sequential* composition — two VMMs issued back-to-back
    on the same datapath — so every field adds, including ``iterations``
    (total cycles, hence a latency count, not a max).  Stats for VMMs that
    run on disjoint crossbars in parallel should instead combine energy
    fields with ``+`` and take ``max`` of ``iterations`` by hand.  (An
    earlier revision documented ``iterations`` as a "max latency proxy"
    while ``__add__`` summed ``max(x, 0)`` terms — i.e. it silently summed;
    the sum semantic is now the documented one and is pinned by tests.)
    """

    conversions: int = 0
    bit_decisions: int = 0
    iterations: int = 0  # total 100ns crossbar cycles (sequential latency)
    skipped_conversions: int = 0

    def __add__(self, other: "ConversionStats") -> "ConversionStats":
        return ConversionStats(
            conversions=self.conversions + other.conversions,
            bit_decisions=self.bit_decisions + other.bit_decisions,
            iterations=self.iterations + other.iterations,
            skipped_conversions=self.skipped_conversions + other.skipped_conversions,
        )


# ---------------------------------------------------------------------------
# Two-limb (radix 2**20) accumulator helpers — jit-safe 39+ bit integers.
# ---------------------------------------------------------------------------

def limb_normalize(hi: jnp.ndarray, lo: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bring ``lo`` into [0, RADIX); works for signed (hi, lo) pairs."""
    carry = lo >> RADIX_BITS  # arithmetic shift == floor division by RADIX
    return hi + carry, lo - (carry << RADIX_BITS)


def limb_add(a, b):
    return limb_normalize(a[0] + b[0], a[1] + b[1])


def limb_sub(a, b):
    return limb_normalize(a[0] - b[0], a[1] - b[1])


def limb_from_int(v: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """int32 value -> normalized limb pair."""
    return limb_normalize(jnp.zeros_like(v), v)


def limb_from_int_shifted(v: jnp.ndarray, shift: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Limb pair holding ``v * 2**shift`` for int32 ``v`` (|v| < 2**30).

    Used by Karatsuba/Strassen recombination where sub-products fit in int32
    but their shifted positions do not.  Exact for signed ``v`` (two's
    complement identity ``v = (v >> k) * 2**k + (v & (2**k - 1))``).
    """
    v = v.astype(jnp.int32)
    if shift >= RADIX_BITS:
        return limb_normalize(v << (shift - RADIX_BITS), jnp.zeros_like(v))
    k = RADIX_BITS - shift
    hi = v >> k  # arithmetic shift: floor(v / 2**k)
    lo = (v & ((1 << k) - 1)) << shift  # < RADIX, non-negative
    return hi, lo


# ---------------------------------------------------------------------------
# Core datapath
# ---------------------------------------------------------------------------

def _grouped_planes(x_codes: jnp.ndarray, spec: CrossbarSpec):
    """DAC view of a padded (B, Kp) input block: (T, B, G, R) planes.

    regroup DAC bits: dac_bits=1 -> T = input_bits planes of 1 bit each;
    otherwise dac_bits consecutive planes combine into one multi-bit level.
    """
    B, Kp = x_codes.shape
    G = Kp // spec.rows
    planes = fxp.bit_planes(x_codes, spec.input_bits)  # (T', B, Kp) with T'=input_bits
    if spec.dac_bits != 1:
        T = spec.n_iters
        pw = (1 << jnp.arange(spec.dac_bits, dtype=jnp.int32)).reshape(1, -1, 1, 1)
        planes = jnp.pad(planes, ((0, T * spec.dac_bits - planes.shape[0]), (0, 0), (0, 0)))
        planes = planes.reshape(T, spec.dac_bits, B, Kp)
        planes = jnp.sum(planes * pw, axis=1)
    return planes.reshape(planes.shape[0], B, G, spec.rows)


def _grouped(x_codes: jnp.ndarray, w_codes: jnp.ndarray, spec: CrossbarSpec):
    """Pad the contraction dim to a multiple of ``spec.rows`` and reshape.

    x_codes: (B, K) unsigned input codes; w_codes: (K, N) *biased* cell codes.
    Returns planes (T, B, G, R), slices (S, G, R, N), n_groups.
    """
    B, K = x_codes.shape
    Kp = -(-K // spec.rows) * spec.rows
    if Kp != K:
        x_codes = jnp.pad(x_codes, ((0, 0), (0, Kp - K)))
        w_codes = jnp.pad(w_codes, ((0, Kp - K), (0, 0)))
    G = Kp // spec.rows
    planes = _grouped_planes(x_codes, spec)
    slices = fxp.cell_slices(w_codes, spec.weight_bits, spec.cell_bits)
    slices = slices.reshape(slices.shape[0], G, spec.rows, w_codes.shape[1])
    return planes, slices, G


def _column_partials(planes: jnp.ndarray, slices: jnp.ndarray) -> jnp.ndarray:
    """All ADC column conversions: (T, S, B, G, N) int32, each <= partial_max."""
    return jnp.einsum(
        "tbgr,sgrn->tsbgn",
        planes.astype(jnp.float32),
        slices.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)


def accumulate_partials(
    partials: jnp.ndarray, spec: CrossbarSpec
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shift-add (T, S, B, G, N) int32 partials into a (B, N) limb pair.

    Shared by the ideal and device-perturbed datapaths: once the column
    conversions exist as integers, the digital shift-and-add tree is the
    same exact two-limb arithmetic either way.
    """
    T, S = partials.shape[0], partials.shape[1]
    t_idx = jnp.arange(T, dtype=jnp.int32) * spec.dac_bits
    s_idx = jnp.arange(S, dtype=jnp.int32) * spec.cell_bits
    base = (t_idx[:, None] + s_idx[None, :]).reshape(T, S, 1, 1, 1)  # (T,S,1,1,1)

    # Split each shifted partial into limbs without overflowing int32:
    # if base < RADIX_BITS: p << base fits in base+adc_bits <= 19+9=28 bits.
    # if base >= RADIX_BITS: contribution is entirely in the hi limb.
    base_lo = jnp.minimum(base, RADIX_BITS - 1)
    shifted = partials << base_lo  # safe
    c_lo = jnp.where(base < RADIX_BITS, shifted & RADIX_MASK, 0)
    c_hi = jnp.where(
        base < RADIX_BITS,
        shifted >> RADIX_BITS,
        partials << jnp.maximum(base - RADIX_BITS, 0),
    )
    # Sum over (t, s) first: <= T*S*2^20 < 2^28 for the lo limb — safe.
    lo_ts = jnp.sum(c_lo, axis=(0, 1))  # (B, G, N)
    hi_ts = jnp.sum(c_hi, axis=(0, 1))
    # Normalize per group, then reduce over groups.
    hi_g, lo_g = limb_normalize(hi_ts, lo_ts)
    hi = jnp.sum(hi_g, axis=1)
    lo = jnp.sum(lo_g, axis=1)  # <= G * 2^20; G <= 2^10 keeps this < 2^31
    return limb_normalize(hi, lo)


def _apply_partial_transform(partials, spec, partial_transform):
    flags = None
    if partial_transform is not None:
        partials, flags = partial_transform(partials, spec)
        if flags is not None:
            flags = jnp.any(flags, axis=(0, 1, 3))  # (B, N)
    return partials, flags


def crossbar_accumulate(
    x_codes: jnp.ndarray,
    w_codes_biased: jnp.ndarray,
    spec: CrossbarSpec,
    partial_transform=None,
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], Optional[jnp.ndarray]]:
    """Run the full analog pipeline, returning the exact accumulator.

    Args:
      x_codes: (B, K) unsigned input codes in [0, 2**input_bits).
      w_codes_biased: (K, N) unsigned cell codes in [0, 2**weight_bits).
      partial_transform: optional ``fn(partials, spec) -> (partials, flags)``
        hook used by the adaptive-ADC model to round/mask each (t, s)
        conversion; ``flags`` (B, N) bool marks columns whose above-window
        MSBs fired (=> clamp), or None.

    Returns:
      ((hi, lo), flags): normalized limb pair of shape (B, N) holding the
      exact (or ADC-transformed) accumulator value; flags as above.
    """
    planes, slices, G = _grouped(x_codes, w_codes_biased, spec)
    partials = _column_partials(planes, slices)  # (T,S,B,G,N)
    partials, flags = _apply_partial_transform(partials, spec, partial_transform)
    return accumulate_partials(partials, spec), flags


def noisy_crossbar_accumulate(
    x_codes: jnp.ndarray,
    g_eff: jnp.ndarray,
    spec: CrossbarSpec,
    partial_transform=None,
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], Optional[jnp.ndarray]]:
    """Analog pipeline against *perturbed* per-slice cell values.

    ``g_eff``: (S, K, N) float32 effective cell codes from
    ``repro.device.models.effective_cell_codes`` — grid-quantized so the f32
    column dot products below are exact (any summation order).  Each column
    conversion is what a real ADC does to the analog bitline current: round
    to the nearest integer code, saturating at ``partial_max``.  From there
    the digital shift-add tree is identical to the ideal path, so a zero-
    noise ``g_eff`` reproduces ``crossbar_accumulate`` bit-for-bit.
    """
    B, K = x_codes.shape
    Kp = -(-K // spec.rows) * spec.rows
    if Kp != K:
        x_codes = jnp.pad(x_codes, ((0, 0), (0, Kp - K)))
        g_eff = jnp.pad(g_eff, ((0, 0), (0, Kp - K), (0, 0)))
    G = Kp // spec.rows
    planes = _grouped_planes(x_codes, spec)
    slices = g_eff.astype(jnp.float32).reshape(g_eff.shape[0], G, spec.rows, g_eff.shape[2])
    # HIGHEST: the cells' 10 significant bits must not be rounded to bf16
    raw = jnp.einsum(
        "tbgr,sgrn->tsbgn",
        planes.astype(jnp.float32),
        slices,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    # ADC sampling of the analog column current: round-half-up, saturating.
    partials = jnp.floor(raw + 0.5).astype(jnp.int32)
    partials = jnp.clip(partials, 0, spec.partial_max)
    partials, flags = _apply_partial_transform(partials, spec, partial_transform)
    return accumulate_partials(partials, spec), flags


def requantize_limbs(
    acc: Tuple[jnp.ndarray, jnp.ndarray],
    spec: CrossbarSpec,
    x_sum: Optional[jnp.ndarray] = None,
    clamp_flags: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Scaling stage: drop ``drop_lsb`` LSBs (round-half-up), clamp to out_bits.

    For signed weights the digital bias correction ``2**(wb-1) * sum(x)`` is
    applied first (``x_sum``: (B,) int32 sum of input codes).
    """
    hi, lo = acc
    if spec.signed_weights:
        assert x_sum is not None
        # bias = x_sum << (weight_bits - 1); decompose into limbs.
        wb = spec.weight_bits - 1
        if wb >= RADIX_BITS:
            b_hi = x_sum << (wb - RADIX_BITS)
            b_lo = jnp.zeros_like(x_sum)
        else:
            b_hi = x_sum >> (RADIX_BITS - wb)
            b_lo = (x_sum << wb) & RADIX_MASK
        hi, lo = limb_normalize(hi - b_hi[:, None], lo - b_lo[:, None])
        out_max = (1 << (spec.out_bits - 1)) - 1
        out_min = -(1 << (spec.out_bits - 1))
    else:
        out_max = (1 << spec.out_bits) - 1
        out_min = 0

    y = _scale_round_clip(hi, lo, spec.drop_lsb, out_min, out_max)
    if clamp_flags is not None:
        y = jnp.where(clamp_flags, out_max, y)
    return y.astype(jnp.int32)


def _scale_round_clip(hi, lo, d: int, out_min: int, out_max: int):
    """Exact round-half-up shift of a normalized limb pair, then clip.

    For d < 20 the value is reassembled with a saturation pre-check; for
    d >= 20: floor((hi*2^20 + lo + 2^(d-1)) / 2^d) = (hi + ((lo+half)>>20))
    >> (d-20), exact because the discarded cross term is < 2^d.
    """
    assert 0 < d
    if d < RADIX_BITS:
        hi_cap = (1 << max((out_max.bit_length() + d) - RADIX_BITS + 1, 1)) + 1
        hi_c = jnp.clip(hi, -hi_cap, hi_cap)
        y = (hi_c << (RADIX_BITS - d)) + ((lo + (1 << (d - 1))) >> d)
        y = jnp.where(hi > hi_cap, out_max, jnp.where(hi < -hi_cap, out_min, y))
    else:
        half = 1 << (d - 1)
        if d - 1 >= 31:
            # half exceeds int32; fold it into the hi limb exactly
            tmp = lo
            hi = hi + (1 << (d - 1 - RADIX_BITS))
        else:
            tmp = lo + half
        H = hi + (tmp >> RADIX_BITS)
        y = H >> (d - RADIX_BITS)
    return jnp.clip(y, out_min, out_max)


def requantize_exact_limbs(
    acc: Tuple[jnp.ndarray, jnp.ndarray], spec: CrossbarSpec, signed_out: bool = True
) -> jnp.ndarray:
    """Scale+clamp a limb accumulator that already holds the exact ``x @ w``
    (bias corrections applied by the caller, e.g. ``signed_vmm_limbs``)."""
    hi, lo = limb_normalize(*acc)
    if signed_out:
        out_max = (1 << (spec.out_bits - 1)) - 1
        out_min = -(1 << (spec.out_bits - 1))
    else:
        out_max = (1 << spec.out_bits) - 1
        out_min = 0
    return _scale_round_clip(hi, lo, spec.drop_lsb, out_min, out_max).astype(jnp.int32)


def layer_scaled_spec(spec: CrossbarSpec, k: int) -> CrossbarSpec:
    """Per-layer output scaling (the paper's "scaling factor" stage).

    The fixed-point format of a layer's output is chosen offline so the
    worst-case accumulator of a K-row dot product fits the ``out_bits``
    window after the shift: drop >= in + w - 1 + ceil(log2 K) - (out - 1).
    """
    need = (
        spec.input_bits
        + spec.weight_bits
        - 1
        + max(0, math.ceil(math.log2(max(2, k))))
        - (spec.out_bits - 1)
    )
    return spec.replace(drop_lsb=max(spec.drop_lsb, need))


def crossbar_vmm(
    x_codes: jnp.ndarray,
    w_codes: jnp.ndarray,
    spec: CrossbarSpec = DEFAULT_SPEC,
    partial_transform=None,
    device=None,
) -> jnp.ndarray:
    """End-to-end crossbar VMM on integer codes.

    x_codes: (..., K) unsigned input codes.  w_codes: (K, N) **signed** codes
    if ``spec.signed_weights`` else unsigned.  Returns (..., N) int32 output
    codes (``out_bits`` wide, signed per spec).

    ``device``: optional ``repro.device.models.DeviceConfig``; when set, the
    weight slab is programmed through the device non-ideality pipeline and
    the VMM runs against the perturbed cells (the ideal config is a no-op).
    A config provisioning ``spare_cols`` additionally routes the slab
    through the fault-aware spare-column repair planner (``device.repair``)
    before the VMM — faulty columns serve from programmed spares.
    """
    batch_shape = x_codes.shape[:-1]
    K = x_codes.shape[-1]
    xb = x_codes.reshape(-1, K).astype(jnp.int32)
    if spec.signed_weights:
        wb = (w_codes.astype(jnp.int32) + spec.weight_bias)
        x_sum = jnp.sum(xb, axis=-1)
    else:
        wb = w_codes.astype(jnp.int32)
        x_sum = None
    if device is not None and not device.is_ideal:
        from repro.device import models as dev_models  # deferred: device imports core

        g_eff = dev_models.effective_cell_codes(wb, spec, device)
        acc, flags = noisy_crossbar_accumulate(xb, g_eff, spec, partial_transform)
    else:
        acc, flags = crossbar_accumulate(xb, wb, spec, partial_transform)
    y = requantize_limbs(acc, spec, x_sum=x_sum, clamp_flags=flags)
    return y.reshape(batch_shape + (w_codes.shape[-1],))


def noisy_crossbar_vmm(
    x_codes: jnp.ndarray,
    g_eff: jnp.ndarray,
    spec: CrossbarSpec = DEFAULT_SPEC,
    partial_transform=None,
) -> jnp.ndarray:
    """Crossbar VMM against precomputed effective cell codes.

    Same contract as ``crossbar_vmm`` but the weights are already programmed:
    ``g_eff`` is the (S, K, N) float32 effective-cell-code array (biased
    representation) — possibly a *repaired* layout with spare-column cells
    already scattered into victim positions (``device.repair``; the datapath
    is column-separable, so nothing downstream can tell).  This is the
    functional oracle for the batched Pallas kernel ``kernels.noisy_vmm``.
    """
    batch_shape = x_codes.shape[:-1]
    K = x_codes.shape[-1]
    xb = x_codes.reshape(-1, K).astype(jnp.int32)
    x_sum = jnp.sum(xb, axis=-1) if spec.signed_weights else None
    acc, flags = noisy_crossbar_accumulate(xb, g_eff, spec, partial_transform)
    y = requantize_limbs(acc, spec, x_sum=x_sum, clamp_flags=flags)
    return y.reshape(batch_shape + (g_eff.shape[-1],))


def signed_vmm_limbs(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: CrossbarSpec,
    signed_inputs: bool = False,
    partial_transform=None,
) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], Optional[jnp.ndarray]]:
    """Exact limb accumulator of ``x @ w`` through the analog pipeline.

    Generalizes the datapath to signed inputs *and* signed weights via offset
    encoding with digital correction (the input-side analogue of ISAAC's
    weight bias): with offsets ``ox = 2**(in_bits-1)``, ``ow = 2**(w_bits-1)``

        sum (x+ox)(w+ow) = sum x w + ox * sum_col(w) + ow * sum(x) + K*ox*ow

    The three correction terms are exact digital computations (the column
    sums of installed weights are precomputed at write time on real hardware).
    Used by Karatsuba/Strassen, which need exact sub-products.

    x: (B, K) int codes; w: (K, N) int codes.  Returns ((hi, lo), flags).
    """
    B, K = x.shape
    ox = (1 << (spec.input_bits - 1)) if signed_inputs else 0
    ow = spec.weight_bias
    xu = (x.astype(jnp.int32) + ox)
    wu = (w.astype(jnp.int32) + ow)
    acc, flags = crossbar_accumulate(xu, wu, spec, partial_transform)
    hi, lo = acc
    # acc = sum_k (x_k + ox)(w_k + ow); peel the offsets digitally:
    # x@w = acc - ox * colsum(w_u) - ow * rowsum(x_u) + K * ox * ow
    N = w.shape[1]
    corr = (jnp.zeros((B, N), jnp.int32), jnp.zeros((B, N), jnp.int32))
    if ox:
        col_wu = jnp.sum(wu, axis=0)  # (N,), <= K * 2**w_bits
        h, l = limb_from_int_shifted(col_wu, spec.input_bits - 1)
        corr = limb_add(corr, (jnp.broadcast_to(h, (B, N)), jnp.broadcast_to(l, (B, N))))
    if ow:
        row_xu = jnp.sum(xu, axis=-1)[:, None]  # (B, 1)
        h, l = limb_from_int_shifted(row_xu, spec.weight_bits - 1)
        corr = limb_add(corr, (jnp.broadcast_to(h, (B, N)), jnp.broadcast_to(l, (B, N))))
    hi, lo = limb_sub((hi, lo), corr)
    if ox and ow:
        kxw = K * ox * ow  # python int, exact
        add_hi = kxw >> RADIX_BITS
        add_lo = kxw & RADIX_MASK
        hi, lo = limb_normalize(hi + add_hi, lo + add_lo)
    return (hi, lo), flags


def plane_activity(
    x_codes: jnp.ndarray, spec: CrossbarSpec, block_m: int = 128
) -> Tuple[int, int]:
    """Row-weighted (active, total) input bit-plane counts for a VMM input.

    Mirrors the Pallas kernels' ``skip_zero_planes`` granularity: the kernel
    skips all S slice-dots of iteration ``t`` for a ``(bm, rows)`` input
    block whose bit-plane is entirely zero, so every row in the block shares
    the skip decision.  One "row-plane" here is (input row, iteration t, row
    group g); each active row-plane costs ``n_cols * n_slices`` ADC
    conversions.  Returns python ints with ``active <= total``;
    ``total * n * n_slices`` is the dense conversion count.
    """
    x2 = x_codes.reshape(-1, x_codes.shape[-1]).astype(jnp.int32)
    B, K = x2.shape
    Kp = -(-K // spec.rows) * spec.rows
    if Kp != K:
        x2 = jnp.pad(x2, ((0, 0), (0, Kp - K)))
    planes = _grouped_planes(x2, spec)  # (T, B, G, R)
    nz = np.asarray(jnp.any(planes != 0, axis=3))  # (T, B, G)
    T, _, G = nz.shape
    bm = min(block_m, max(8, B))  # the kernel wrappers' block choice
    active = 0
    for start in range(0, B, bm):
        rows = min(start + bm, B) - start
        blk = nz[:, start : start + bm, :].any(axis=1)  # (T, G)
        active += int(blk.sum()) * rows
    return active, T * G * B


def conversion_stats(
    batch: int,
    k: int,
    n: int,
    spec: CrossbarSpec,
    bits_per_conversion: Optional[float] = None,
    x_codes: Optional[jnp.ndarray] = None,
    block_m: int = 128,
) -> ConversionStats:
    """ADC work for one VMM of shape (batch, k) x (k, n).

    With ``x_codes`` (the actual unsigned input codes) the count becomes
    activity-aware: conversions belonging to all-zero input bit-planes — the
    ones ``skip_zero_planes`` kernels never issue and a zero-plane-aware ADC
    never samples — move to ``skipped_conversions``.
    """
    groups = -(-k // spec.rows)
    convs = batch * n * groups * spec.n_iters * spec.n_slices
    skipped = 0
    if x_codes is not None:
        active, total = plane_activity(x_codes, spec, block_m=block_m)
        if total != batch * spec.n_iters * groups:
            raise ValueError(
                f"x_codes {x_codes.shape} inconsistent with batch={batch}, k={k}"
            )
        active_convs = active * n * spec.n_slices
        skipped = convs - active_convs
        convs = active_convs
    bits = bits_per_conversion if bits_per_conversion is not None else spec.adc_bits
    return ConversionStats(
        conversions=convs,
        bit_decisions=int(round(convs * bits)),
        iterations=spec.n_iters,
        skipped_conversions=skipped,
    )


# ---------------------------------------------------------------------------
# Float-level convenience API (used by models.CrossbarLinear and examples)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Symmetric-ish affine quantization of a float matmul onto the datapath."""

    x_scale: float  # x_code = round(x / x_scale), unsigned
    w_scale: float  # w_code = round(w / w_scale), signed
    out_frac_shift: int = 0  # extra output shift folded into drop_lsb


def quantize_input(x: jnp.ndarray, spec: CrossbarSpec, x_scale: float) -> jnp.ndarray:
    q = jnp.round(x / x_scale)
    return jnp.clip(q, 0, (1 << spec.input_bits) - 1).astype(jnp.int32)


def quantize_weight(w: jnp.ndarray, spec: CrossbarSpec, w_scale: float) -> jnp.ndarray:
    q = jnp.round(w / w_scale)
    lim = 1 << (spec.weight_bits - 1)
    return jnp.clip(q, -lim, lim - 1).astype(jnp.int32)


def crossbar_matmul_f32(
    x: jnp.ndarray,
    w: jnp.ndarray,
    spec: CrossbarSpec = DEFAULT_SPEC,
    qp: Optional[QuantParams] = None,
    partial_transform=None,
    device=None,
) -> jnp.ndarray:
    """Quantize float operands, run the crossbar pipeline, dequantize.

    A float reference for a CrossbarLinear layer: ``y ~ x @ w`` with ISAAC
    fixed-point semantics.  ``x`` must be non-negative (post-ReLU/softmax
    style) unless callers offset-encode.
    """
    spec = layer_scaled_spec(spec, x.shape[-1])
    if qp is None:
        x_scale = jnp.maximum(jnp.max(x), 1e-9) / ((1 << spec.input_bits) - 1)
        w_scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9) / ((1 << (spec.weight_bits - 1)) - 1)
    else:
        x_scale, w_scale = qp.x_scale, qp.w_scale
    xq = quantize_input(x, spec, x_scale)
    wq = quantize_weight(w, spec, w_scale)
    yq = crossbar_vmm(xq, wq, spec, partial_transform=partial_transform, device=device)
    return yq.astype(jnp.float32) * (x_scale * w_scale * (2.0 ** spec.drop_lsb))


def exact_vmm_reference(x_codes: np.ndarray, w_codes: np.ndarray, spec: CrossbarSpec) -> np.ndarray:
    """Numpy int64 oracle for the full datapath (used by tests only)."""
    x = x_codes.astype(np.int64)
    w = w_codes.astype(np.int64)
    total = x @ w  # exact in int64
    d = spec.drop_lsb
    y = (total + (1 << (d - 1))) >> d
    if spec.signed_weights:
        out_max, out_min = (1 << (spec.out_bits - 1)) - 1, -(1 << (spec.out_bits - 1))
    else:
        out_max, out_min = (1 << spec.out_bits) - 1, 0
    return np.clip(y, out_min, out_max)
