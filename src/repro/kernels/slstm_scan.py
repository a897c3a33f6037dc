"""Fused sLSTM recurrence as a Pallas TPU kernel (EXPERIMENTS.md §Perf, cell 1).

The sLSTM scan is strictly sequential; under XLA each of the S steps re-reads
the four (H, dh, dh) recurrent matrices from HBM — ~33 MB x 4096 steps
~ 137 GB per device per training step, the dominant memory-roofline term of
xlstm-350m after the pure-DP layout fix.

This kernel pins the recurrent matrices (8 MB bf16) and the (c, n, h) state
in VMEM and streams only the per-step pre-activations: grid (B, S) with the
sequence axis innermost ("arbitrary" semantics), Pallas pipelining keeps the
constant-index R blocks resident, and per-step HBM traffic drops to the
x-projection stream (4*H*dh values in, H*dh out).

Validated in interpret mode against the pure-jnp scan (ref:
``models.xlstm.slstm_block``) — see tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IGATE_CLIP = 5.0


def _kernel(pre_ref, rz_ref, ri_ref, rf_ref, ro_ref, c0_ref, n0_ref, h0_ref,
            h_out_ref, c_out_ref, n_out_ref, hn_out_ref, state, *, seq_len: int):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        state[0] = c0_ref[0].astype(jnp.float32)
        state[1] = n0_ref[0].astype(jnp.float32)
        state[2] = h0_ref[0].astype(jnp.float32)

    c_, n_, h_ = state[0], state[1], state[2]  # (H, dh) f32
    pre = pre_ref[0, 0].astype(jnp.float32)  # (4, H, dh)

    def rec(r_ref):
        # (H, dh) x (H, dh, dh) -> (H, dh), batched over heads
        return jax.lax.dot_general(
            h_.astype(jnp.float32), r_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    z = jnp.tanh(pre[0] + rec(rz_ref))
    i = jnp.exp(jnp.minimum(pre[1] + rec(ri_ref), IGATE_CLIP))
    f = jax.nn.sigmoid(pre[2] + rec(rf_ref))
    o = jax.nn.sigmoid(pre[3] + rec(ro_ref))
    c1 = f * c_ + i * z
    n1 = f * n_ + i
    h1 = o * c1 / jnp.maximum(n1, 1.0)
    state[0], state[1], state[2] = c1, n1, h1
    h_out_ref[0, 0] = h1.astype(h_out_ref.dtype)

    @pl.when(s == seq_len - 1)
    def _final():
        c_out_ref[0] = c1.astype(c_out_ref.dtype)
        n_out_ref[0] = n1.astype(n_out_ref.dtype)
        hn_out_ref[0] = h1.astype(hn_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def slstm_scan_pallas(pre, r_z, r_i, r_f, r_o, c0, n0, h0, interpret: bool = False):
    """pre: (B, S, 4, H, dh); r_*: (H, dh, dh); c0/n0/h0: (B, H, dh).

    Returns (h_all (B, S, H, dh), c1, n1, h1)."""
    B, S, _, H, dh = pre.shape
    kernel = functools.partial(_kernel, seq_len=S)
    grid = (B, S)
    r_spec = pl.BlockSpec((H, dh, dh), lambda b, s: (0, 0, 0))
    st_spec = pl.BlockSpec((1, H, dh), lambda b, s: (b, 0, 0))
    out = pl.pallas_call(
        kernel,
        name="slstm_scan_pallas",  # the kernel's name in HLO and in profiles
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 4, H, dh), lambda b, s: (b, s, 0, 0, 0)),
            r_spec, r_spec, r_spec, r_spec,
            st_spec, st_spec, st_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, H, dh), lambda b, s: (b, s, 0, 0)),
            st_spec, st_spec, st_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, dh), pre.dtype),
            jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((3, H, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pre, r_z, r_i, r_f, r_o, c0, n0, h0)
    return out[0], out[1], out[2], out[3]
