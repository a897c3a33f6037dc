"""Pallas TPU kernel for the Newton crossbar VMM datapath.

This is the compute hot spot of the paper adapted to the TPU memory
hierarchy: a 128x128 memristor crossbar tile maps exactly onto an MXU-aligned
128x128 block held in VMEM.  Per (row-group k) block the kernel streams the
16 input bit-planes (generated in-register from the int32 activation block —
the "1-bit DAC"), multiplies them against the 8 weight bit-slices (extracted
in-register from the int32 weight block — the "2-bit cells"), applies the
per-(t, s) adaptive-ADC transform (static shift/clamp tables baked in at
trace time), and shift-adds everything into a two-limb (radix 2**20) int32
accumulator pair held in VMEM scratch — the same exact-arithmetic strategy as
``core.crossbar``.

Two kernels:

* ``crossbar_vmm`` — the paper-faithful datapath: T x S = 128 MXU dots of
  (bm, 128) x (128, bn) per block, each a {0,1} x {0..3} product (exact in
  f32 by a large margin), with the ADC transform applied per conversion.
* ``crossbar_vmm_fast`` — exact fused path when no ADC transform is needed
  (full-resolution ADCs), where slices merge: activations split into two
  8-bit halves, the biased weight code into byte-wide chunks of whole cells
  (``fast_chunk_bits``), so 2 x 2 = 4 bf16 dots per block.  Bytes are exact
  in bf16 and each dot's sum is at most 255 * 255 * 128 < 2**24, so the f32
  accumulator is exact in any order.

Grid is (M/bm, N/bn, K/bk) with bk = rows = 128 (the ADC row-group); the
k axis is the innermost reduction ("arbitrary" semantics).  Both kernels are
validated in interpret mode against ``ref.crossbar_vmm_ref`` across shape /
guard sweeps (tests/test_kernels.py) — bit-identical outputs.

Both kernels are column-separable (bitline j reads only weight column j),
which is what lets ``device.repair`` bake spare-column repairs into the
weight layout at programming time instead of gathering kernel outputs —
tests/test_repair.py pins gather-commutation down bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.adc import ADCConfig, window
from repro.core.crossbar import CrossbarSpec, DEFAULT_SPEC, RADIX_BITS, RADIX_MASK

DEFAULT_BM = 128
DEFAULT_BN = 128


def _schedule_tables(spec: CrossbarSpec, cfg: Optional[ADCConfig]):
    """Static per-(t, s) LSB shift and MSB detect tables (python ints)."""
    T, S = spec.n_iters, spec.n_slices
    if cfg is None or cfg.mode == "full":
        return [[0] * S for _ in range(T)], [[None] * S for _ in range(T)]
    lo, hi = window(spec, cfg)
    shifts, detects = [], []
    for t in range(T):
        srow, drow = [], []
        for s in range(S):
            base = spec.base_shift(t, s)
            srow.append(int(np.clip(lo - base, 0, spec.adc_bits)))
            hi_rel = hi - base
            # MSB detect is only sound on the unsigned datapath (see adc.py)
            if cfg.msb_clamp and hi_rel < spec.adc_bits and not spec.signed_weights:
                drow.append(int(hi_rel))
            else:
                drow.append(None)
        shifts.append(srow)
        detects.append(drow)
    return shifts, detects


def _vmm_kernel(
    x_ref, w_ref, xsum_ref, o_ref, acc_hi, acc_lo, flag_ref, *,
    spec: CrossbarSpec, shifts, detects, n_k: int, skip_zero_planes: bool,
):
    """One (bm, bn) output block; k-axis accumulates row groups.

    With ``skip_zero_planes`` the T x S dot loop is predicated per iteration
    ``t`` on the plane popcount: an all-zero input bit-plane produces only
    zero partials (and zero ADC/flag effects — a rounded/clamped 0 is 0), so
    a real adaptive ADC never samples it (Ibrayev et al.) and the kernel
    skips all S dots for that plane.  Bit-identical to the dense loop; on
    post-ReLU activations most high planes are dead, so the win is large.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)
        flag_ref[...] = jnp.zeros_like(flag_ref)

    x = x_ref[...]  # (bm, bk) int32 unsigned codes
    w = w_ref[...]  # (bk, bn) int32 biased cell codes
    T, S = spec.n_iters, spec.n_slices
    cell_mask = (1 << spec.cell_bits) - 1
    dac_mask = (1 << spec.dac_bits) - 1

    for t in range(T):
        plane_i = (x >> (t * spec.dac_bits)) & dac_mask

        def _accum(plane_i=plane_i, t=t):
            plane = plane_i.astype(jnp.float32)
            hi_acc = acc_hi[...]
            lo_acc = acc_lo[...]
            flags = flag_ref[...]
            for s in range(S):
                sl = ((w >> (s * spec.cell_bits)) & cell_mask).astype(jnp.float32)
                # {0..dac_max} x {0..3} over 128 rows: exact in f32 (<= 2**9)
                p = jax.lax.dot_general(
                    plane, sl, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).astype(jnp.int32)
                g = shifts[t][s]
                if g > 0:  # SAR skips LSBs below the window: round-half-up
                    p = ((p + (1 << (g - 1))) >> g) << g
                d = detects[t][s]
                if d is not None:  # overflow-detect comparison -> clamp signal
                    flags = jnp.maximum(flags, ((p >> d) > 0).astype(jnp.int32))
                base = spec.base_shift(t, s)
                if base < RADIX_BITS:
                    sh = p << base  # <= 2**(19 + adc_bits) — safe
                    lo_acc = lo_acc + (sh & RADIX_MASK)
                    hi_acc = hi_acc + (sh >> RADIX_BITS)
                else:
                    hi_acc = hi_acc + (p << (base - RADIX_BITS))
            # normalize per plane so limbs stay far from overflow
            carry = lo_acc >> RADIX_BITS
            acc_hi[...] = hi_acc + carry
            acc_lo[...] = lo_acc - (carry << RADIX_BITS)
            flag_ref[...] = flags

        if skip_zero_planes:
            pl.when(jnp.any(plane_i != 0))(_accum)
        else:
            _accum()

    @pl.when(k == n_k - 1)
    def _finalize():
        _requantize_block(o_ref, acc_hi, acc_lo, flag_ref, xsum_ref, spec)


def _requantize_block(o_ref, acc_hi, acc_lo, flag_ref, xsum_ref, spec: CrossbarSpec):
    hi = acc_hi[...]
    lo = acc_lo[...]
    if spec.signed_weights:
        xs = xsum_ref[...]  # (bm, 1) int32 sum of input codes
        wb = spec.weight_bits - 1
        if wb >= RADIX_BITS:
            b_hi = xs << (wb - RADIX_BITS)
            b_lo = jnp.zeros_like(xs)
        else:
            b_hi = xs >> (RADIX_BITS - wb)
            b_lo = (xs << wb) & RADIX_MASK
        hi = hi - b_hi
        lo = lo - b_lo
        out_max = (1 << (spec.out_bits - 1)) - 1
        out_min = -(1 << (spec.out_bits - 1))
    else:
        out_max = (1 << spec.out_bits) - 1
        out_min = 0
    carry = lo >> RADIX_BITS
    hi = hi + carry
    lo = lo - (carry << RADIX_BITS)
    d = spec.drop_lsb
    if d < RADIX_BITS:
        hi_cap = (1 << max(spec.out_bits + d - RADIX_BITS, 1)) + 1
        hi_c = jnp.clip(hi, -hi_cap, hi_cap)
        y = (hi_c << (RADIX_BITS - d)) + ((lo + (1 << (d - 1))) >> d)
        y = jnp.where(hi > hi_cap, out_max, jnp.where(hi < -hi_cap, out_min, y))
    else:
        # exact for d >= 20: see core.crossbar._scale_round_clip
        if d - 1 >= 31:
            tmp = lo
            hi = hi + (1 << (d - 1 - RADIX_BITS))
        else:
            tmp = lo + (1 << (d - 1))
        y = (hi + (tmp >> RADIX_BITS)) >> (d - RADIX_BITS)
    y = jnp.clip(y, out_min, out_max)
    y = jnp.where(flag_ref[...] > 0, out_max, y)
    o_ref[...] = y.astype(jnp.int32)


def fast_chunk_bits(spec: CrossbarSpec) -> Optional[int]:
    """Width of the weight chunks the fast kernel multiplies at once.

    The widest chunk of whole cells, at most 8 bits, whose dot with an input
    half stays exact: operands up to 255 are exact in bf16, and a dot's sum
    of nonnegative products stays below 2**24, so the f32 accumulator is
    exact in any order: (2**half - 1) * (2**c - 1) * rows < 2**24.  8 for
    ``DEFAULT_SPEC`` (two chunks).  None where no such chunk exists (an input
    half or a cell over 8 bits, or too many rows).
    """
    half = spec.input_bits // 2
    if half > 8:
        return None
    fits = [
        c for c in range(spec.cell_bits, 9, spec.cell_bits)
        if ((1 << half) - 1) * ((1 << c) - 1) * spec.rows < 1 << 24
    ]
    return max(fits, default=None)


def _fast_kernel(x_ref, w_ref, xsum_ref, o_ref, acc_hi, acc_lo, flag_ref, *,
                 spec: CrossbarSpec, n_k: int):
    """Fused exact path: 2 input halves x byte-wide weight chunks per block.

    With the full-resolution ADC the per-slice transform is the identity, so
    slices merge: the biased weight code splits into ``fast_chunk_bits``-wide
    chunks (two bytes by default: 4 bf16 dots per block, each bounded by
    255 * 255 * 128 < 2**23).  Where the spec admits no such chunk, one f32
    dot per slice.

    No dot is predicated on its input half's popcount: the vector-to-scalar
    branch costs more on the chip than all four dots of a block.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)
        flag_ref[...] = jnp.zeros_like(flag_ref)

    x = x_ref[...]
    w = w_ref[...]
    c = fast_chunk_bits(spec)
    dtype = jnp.bfloat16
    if c is None:
        c, dtype = spec.cell_bits, jnp.float32
    chunks = [
        ((w >> (j * c)) & ((1 << c) - 1)).astype(dtype)
        for j in range(-(-spec.weight_bits // c))
    ]
    half = spec.input_bits // 2
    hmask = (1 << half) - 1
    hi_acc = acc_hi[...]
    lo_acc = acc_lo[...]
    for hx, xbits in ((0, x & hmask), (half, (x >> half) & hmask)):
        xo = xbits.astype(dtype)
        for j, wc in enumerate(chunks):
            p = jax.lax.dot_general(
                xo, wc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
            base = hx + j * c
            if base < RADIX_BITS:
                # p < 2**24, so split before shifting to stay in int32:
                # p * 2**base = (p >> k) * 2**20 + (p & (2**k - 1)) * 2**base.
                # Each lo term is < 2**20: the lo limb stays below
                # (1 + 2 * n_chunks) * 2**20 until the carry below.
                k_bits = RADIX_BITS - base
                hi_acc = hi_acc + (p >> k_bits)
                lo_acc = lo_acc + ((p & ((1 << k_bits) - 1)) << base)
            else:
                # at most the block's total over 2**20: < 2**(acc_bits - 20)
                hi_acc = hi_acc + (p << (base - RADIX_BITS))
    carry = lo_acc >> RADIX_BITS
    acc_hi[...] = hi_acc + carry
    acc_lo[...] = lo_acc - (carry << RADIX_BITS)

    @pl.when(k == n_k - 1)
    def _finalize():
        _requantize_block(o_ref, acc_hi, acc_lo, flag_ref, xsum_ref, spec)


def _pad_to(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "adc_cfg", "block_m", "block_n", "fast", "interpret",
        "skip_zero_planes",
    ),
)
def crossbar_vmm_pallas(
    x_codes: jnp.ndarray,
    w_codes: jnp.ndarray,
    spec: CrossbarSpec = DEFAULT_SPEC,
    adc_cfg: Optional[ADCConfig] = None,
    block_m: int = DEFAULT_BM,
    block_n: int = DEFAULT_BN,
    fast: bool = False,
    interpret: bool = False,
    skip_zero_planes: bool = True,
) -> jnp.ndarray:
    """Crossbar VMM on integer codes via the Pallas kernel.

    x_codes: (..., K) unsigned input codes; w_codes: (K, N) signed codes when
    ``spec.signed_weights``.  Returns (..., N) int32 output codes identical
    to ``repro.core.crossbar.crossbar_vmm``.

    ``skip_zero_planes``: predicate each input bit-plane's dots on its
    popcount (``@pl.when``); bit-identical either way, faster on sparse
    inputs.  The fast kernel has no such branch: on the chip the branch
    costs more than its four dots.  ``core.crossbar.plane_activity`` counts the skipped
    conversions for the energy model.
    """
    batch_shape = x_codes.shape[:-1]
    K = x_codes.shape[-1]
    N = w_codes.shape[-1]
    x = x_codes.reshape(-1, K).astype(jnp.int32)
    M = x.shape[0]
    w = w_codes.astype(jnp.int32) + spec.weight_bias

    bm = min(block_m, max(8, M))
    bn = min(block_n, N)
    bk = spec.rows

    xs = jnp.sum(x, axis=-1, keepdims=True)  # (M, 1) before padding
    x = _pad_to(_pad_to(x, 0, bm), 1, bk)
    xs = _pad_to(xs, 0, bm)
    w = _pad_to(_pad_to(w, 0, bk), 1, bn)
    # Padded K rows hold cell code 0 and x code 0: zero contribution.
    Mp, Kp = x.shape
    Np = w.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)

    shifts, detects = _schedule_tables(spec, adc_cfg)
    if fast:
        if adc_cfg is not None and adc_cfg.mode != "full":
            raise ValueError("fast path models full-resolution ADCs only")
        kernel = functools.partial(_fast_kernel, spec=spec, n_k=grid[2])
    else:
        kernel = functools.partial(
            _vmm_kernel, spec=spec, shifts=shifts, detects=detects, n_k=grid[2],
            skip_zero_planes=skip_zero_planes,
        )

    out = pl.pallas_call(
        kernel,
        name="crossbar_vmm_pallas",  # the kernel's name in HLO and in profiles
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),  # accumulator hi limb
            pltpu.VMEM((bm, bn), jnp.int32),  # accumulator lo limb
            pltpu.VMEM((bm, bn), jnp.int32),  # ADC overflow clamp flags
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, xs)
    return out[:M, :N].reshape(batch_shape + (N,))
