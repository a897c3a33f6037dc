"""Batched Pallas kernel for the device-perturbed crossbar VMM.

Same structure as ``crossbar_vmm``'s paper-faithful kernel — grid (M/bm,
N/bn, K/bk) with bk = rows, T x S MXU dots per block, two-limb (radix 2**20)
int32 accumulator in VMEM scratch — but the weight operand is the *effective
cell code* array from ``repro.device``: (S, K, N) float32, one perturbed
value per (slice, wordline, bitline) instead of S bit-slices re-derived from
an int32 block in-register.  Each dot is a {0..dac_max} x [0, cell_max]
product; the ADC stage rounds the analog column sum half-up to an integer
code and saturates at ``partial_max``, after which the shift-add tree is the
exact integer arithmetic shared with the ideal kernel.

Exactness argument (why the kernel is validated bit-identical, not
allclose, against ``core.crossbar.noisy_crossbar_vmm``): effective codes are
quantized to a ``2**-GEFF_FRAC_BITS`` grid, so every partial product and
every partial sum is a multiple of the grid step bounded by ``partial_max``
— all exactly representable in float32 (``partial_max << GEFF_FRAC_BITS <
2**24``), making f32 accumulation order-independent.  The adaptive-ADC
shift/clamp tables from ``crossbar_vmm`` apply unchanged, so noise sweeps
can compare full vs adaptive ADC configs on identical perturbed cells.

Spare-column repair (``device.repair``) needs no kernel support: the
datapath is column-separable (bitline j only reads ``g_eff[:, :, j]``), so
the repaired layout — spare cells scattered into victim columns at
programming time — is just another ``g_eff`` and the kernel serves it with
zero steady-state overhead.  tests/test_repair.py pins the equivalence to
an explicit physical-layout + output-gather formulation bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.adc import ADCConfig
from repro.core.crossbar import CrossbarSpec, DEFAULT_SPEC, RADIX_BITS, RADIX_MASK
from repro.device.models import GEFF_FRAC_BITS
from repro.kernels.crossbar_vmm import (
    DEFAULT_BM,
    DEFAULT_BN,
    _pad_to,
    _requantize_block,
    _schedule_tables,
)


def _noisy_kernel(
    x_ref, g_ref, xsum_ref, o_ref, acc_hi, acc_lo, flag_ref, *,
    spec: CrossbarSpec, shifts, detects, n_k: int, skip_zero_planes: bool,
):
    """One (bm, bn) output block against perturbed cells; k accumulates groups.

    ``skip_zero_planes``: as in ``crossbar_vmm._vmm_kernel`` — an all-zero
    input bit-plane drives zero current into every bitline regardless of the
    perturbed cell values (0 * g == 0, and the ADC's round/saturate of 0 is
    0), so its S dots are skipped under a ``@pl.when`` popcount predicate.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)
        flag_ref[...] = jnp.zeros_like(flag_ref)

    x = x_ref[...]  # (bm, bk) int32 unsigned codes
    g = g_ref[...]  # (S, bk, bn) f32 effective cell codes
    T, S = spec.n_iters, spec.n_slices
    dac_mask = (1 << spec.dac_bits) - 1

    for t in range(T):
        plane_i = (x >> (t * spec.dac_bits)) & dac_mask

        def _accum(plane_i=plane_i, t=t):
            plane = plane_i.astype(jnp.float32)
            hi_acc = acc_hi[...]
            lo_acc = acc_lo[...]
            flags = flag_ref[...]
            for s in range(S):
                # grid-quantized cells keep this dot exact in f32 (module
                # doc); their 10 significant bits need full f32 operands
                raw = jax.lax.dot_general(
                    plane, g[s], (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )
                # ADC sampling: round-half-up to an integer code, saturating
                p = jnp.floor(raw + 0.5).astype(jnp.int32)
                p = jnp.clip(p, 0, spec.partial_max)
                gsh = shifts[t][s]
                if gsh > 0:  # SAR skips LSBs below the window: round-half-up
                    p = ((p + (1 << (gsh - 1))) >> gsh) << gsh
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


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "adc_cfg", "block_m", "block_n", "interpret", "skip_zero_planes",
    ),
)
def noisy_vmm_pallas(
    x_codes: jnp.ndarray,
    g_eff: jnp.ndarray,
    spec: CrossbarSpec = DEFAULT_SPEC,
    adc_cfg: Optional[ADCConfig] = None,
    block_m: int = DEFAULT_BM,
    block_n: int = DEFAULT_BN,
    interpret: bool = False,
    skip_zero_planes: bool = True,
) -> jnp.ndarray:
    """Device-perturbed crossbar VMM via the Pallas kernel.

    x_codes: (..., K) unsigned input codes; g_eff: (S, K, N) float32
    effective cell codes (``repro.device.models.effective_cell_codes``).
    Returns (..., N) int32 output codes identical to
    ``repro.core.crossbar.noisy_crossbar_vmm``; ``skip_zero_planes`` is the
    bit-identical plane-popcount early-out (see ``crossbar_vmm``).
    """
    if spec.partial_max << GEFF_FRAC_BITS >= 1 << 24:
        raise ValueError(
            f"partial_max {spec.partial_max} too wide for exact f32 sums at "
            f"{GEFF_FRAC_BITS} fractional bits"
        )
    batch_shape = x_codes.shape[:-1]
    K = x_codes.shape[-1]
    S, Kg, N = g_eff.shape
    if Kg != K or S != spec.n_slices:
        raise ValueError(f"g_eff shape {g_eff.shape} != ({spec.n_slices}, {K}, N)")
    x = x_codes.reshape(-1, K).astype(jnp.int32)
    M = x.shape[0]
    g = g_eff.astype(jnp.float32)

    bm = min(block_m, max(8, M))
    bn = min(block_n, N)
    bk = spec.rows

    xs = jnp.sum(x, axis=-1, keepdims=True)  # (M, 1) before padding
    x = _pad_to(_pad_to(x, 0, bm), 1, bk)
    xs = _pad_to(xs, 0, bm)
    g = _pad_to(_pad_to(g, 1, bk), 2, bn)
    # Padded K rows hold x code 0: zero planes, zero contribution.
    Mp, Kp = x.shape
    Np = g.shape[2]
    grid = (Mp // bm, Np // bn, Kp // bk)

    shifts, detects = _schedule_tables(spec, adc_cfg)
    kernel = functools.partial(
        _noisy_kernel, spec=spec, shifts=shifts, detects=detects, n_k=grid[2],
        skip_zero_planes=skip_zero_planes,
    )

    out = pl.pallas_call(
        kernel,
        name="noisy_vmm_pallas",  # the kernel's name in HLO and in profiles
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((S, bk, bn), lambda i, j, k: (0, k, j)),
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
    )(x, g, xs)
    return out[:M, :N].reshape(batch_shape + (N,))
