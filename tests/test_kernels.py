"""Pallas kernel validation: interpret-mode execution vs the pure-jnp oracle
across shape/dtype/ADC-config sweeps (bit-identical, not just allclose)."""
import functools
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _propcheck import integers, sweep

from repro.core import adc
from repro.core.crossbar import CrossbarSpec, DEFAULT_SPEC, layer_scaled_spec
from repro.device import DeviceConfig, effective_cell_codes
from repro.kernels import ops, ref
from repro.kernels.crossbar_vmm import crossbar_vmm_pallas, fast_chunk_bits

SPEC_S = DEFAULT_SPEC
SPEC_U = DEFAULT_SPEC.replace(signed_weights=False)


def _data(rng, B, K, N, signed=True):
    x = rng.integers(0, 1 << 16, size=(B, K))
    lim = (1 << 15) if signed else (1 << 16)
    lo = -(1 << 15) if signed else 0
    w = rng.integers(lo, lim, size=(K, N))
    return jnp.asarray(x), jnp.asarray(w)


@pytest.mark.parametrize(
    "shape",
    [
        (1, 128, 8),
        (4, 128, 16),
        (3, 300, 40),
        pytest.param((130, 257, 129), marks=pytest.mark.slow),
        (2, 64, 256),
        pytest.param((16, 1024, 64), marks=pytest.mark.slow),
    ],
)
def test_kernel_matches_ref_shapes(shape):
    rng = np.random.default_rng(sum(shape))
    x, w = _data(rng, *shape)
    y_k = ops.crossbar_vmm_op(x, w, SPEC_S, interpret=True)
    y_r = ref.crossbar_vmm_ref(x, w, SPEC_S)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def _worst_case(rng, B, K, N, spec):
    """Every input code at its top and every weight at one of its extremes:
    the first column all at the top, so each of its dots reaches the bound."""
    x = np.full((B, K), (1 << spec.input_bits) - 1)
    lo = -(1 << (spec.weight_bits - 1)) if spec.signed_weights else 0
    hi = lo + (1 << spec.weight_bits) - 1
    w = np.where(rng.random((K, N)) < 0.5, lo, hi)
    w[:, 0] = hi
    return jnp.asarray(x), jnp.asarray(w)


# (spec, B, K, N, data); the first two cases keep the old test's ids
_FAST_CASES = {
    "shape0": (SPEC_S, 4, 128, 16, "random"),
    "shape1": (SPEC_S, 3, 300, 40, "random"),
    "decode_m32": (layer_scaled_spec(SPEC_S, 128), 32, 128, 256, "random"),
    "padded_k960": (layer_scaled_spec(SPEC_S, 960), 4, 960, 16, "random"),
    "prefill_m768": (layer_scaled_spec(SPEC_S, 128), 768, 128, 16, "random"),
    "worst_case": (layer_scaled_spec(SPEC_S, 256), 8, 256, 32, "worst"),
    "worst_case_unsigned": (layer_scaled_spec(SPEC_U, 256), 8, 256, 32, "worst"),
    "unsigned": (layer_scaled_spec(SPEC_U, 300), 3, 300, 40, "random"),
    "rows512_chunk6": (layer_scaled_spec(SPEC_S.replace(rows=512), 600), 4, 600, 24, "worst"),
    "cell1_chunk8": (layer_scaled_spec(SPEC_S.replace(cell_bits=1), 200), 4, 200, 24, "random"),
    "cell3_chunk6": (layer_scaled_spec(SPEC_S.replace(cell_bits=3), 200), 4, 200, 24, "random"),
    "input18_per_slice": (
        layer_scaled_spec(SPEC_S.replace(input_bits=18), 200), 4, 200, 24, "random"
    ),
}


@pytest.mark.parametrize("case", list(_FAST_CASES))
def test_fast_kernel_matches_ref(case):
    spec, B, K, N, data = _FAST_CASES[case]
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if data == "worst":
        x, w = _worst_case(rng, B, K, N, spec)
    else:
        x = jnp.asarray(rng.integers(0, 1 << spec.input_bits, size=(B, K)))
        lo = -(1 << (spec.weight_bits - 1)) if spec.signed_weights else 0
        w = jnp.asarray(rng.integers(lo, lo + (1 << spec.weight_bits), size=(K, N)))
    y_k = ops.crossbar_vmm_op(x, w, spec, fast=True, interpret=True)
    y_r = ref.crossbar_vmm_ref(x, w, spec)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


def _count_dots(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "dot_general"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_dots(inner)
    return n


def test_fast_chunk_width_bound():
    """The weight chunk width keeps every fast-kernel dot exact: operands
    at most 255 (exact in bf16) and each dot's sum below 2**24 (exact in the
    f32 accumulator), and no wider chunk of whole cells would be."""

    def bound(spec, c):
        return ((1 << (spec.input_bits // 2)) - 1) * ((1 << c) - 1) * spec.rows

    for rows in (16, 64, 128, 256, 512, 1024, 2048, 4096, 1 << 16):
        for cell_bits in (1, 2, 3, 4, 8, 16):
            for weight_bits in (4, 8, 12, 16):
                for input_bits in (4, 8, 15, 16, 17, 18, 20):
                    spec = CrossbarSpec(
                        rows=rows, cell_bits=cell_bits, weight_bits=weight_bits,
                        input_bits=input_bits,
                    )
                    c = fast_chunk_bits(spec)
                    if c is None:  # per-slice f32 dots: no exact chunk exists
                        assert (
                            input_bits // 2 > 8 or cell_bits > 8
                            or bound(spec, cell_bits) >= 1 << 24
                        ), spec
                        continue
                    assert c % cell_bits == 0 and cell_bits <= c <= 8, spec
                    assert input_bits // 2 <= 8, spec
                    assert bound(spec, c) < 1 << 24, spec
                    assert c + cell_bits > 8 or bound(spec, c + cell_bits) >= 1 << 24, spec
    # the default datapath: two byte-wide chunks, 2 halves x 2 = 4 dots a block
    assert fast_chunk_bits(DEFAULT_SPEC) == 8
    x = jax.ShapeDtypeStruct((32, 960), jnp.int32)
    w = jax.ShapeDtypeStruct((960, 5120), jnp.int32)
    jaxpr = jax.make_jaxpr(
        functools.partial(crossbar_vmm_pallas, spec=DEFAULT_SPEC, fast=True)
    )(x, w)
    assert _count_dots(jaxpr.jaxpr) == 4


@pytest.mark.parametrize("cfg", [adc.SAFE_ADAPTIVE, adc.EXACT_ADAPTIVE])
@pytest.mark.parametrize("signed", [True, False])
def test_kernel_adaptive_adc(cfg, signed):
    rng = np.random.default_rng(13 + signed)
    spec = SPEC_S if signed else SPEC_U
    x, w = _data(rng, 8, 384, 32, signed=signed)
    y_k = ops.crossbar_vmm_op(x, w, spec, adc_cfg=cfg, interpret=True)
    y_r = ref.crossbar_vmm_ref(x, w, spec, adc_cfg=cfg)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize(
    "spec",
    [
        CrossbarSpec(weight_bits=8, input_bits=8, out_bits=8, drop_lsb=7),
        CrossbarSpec(cell_bits=4, dac_bits=2),
        CrossbarSpec(rows=64),
    ],
    ids=["w8a8", "cell4dac2", "rows64"],
)
def test_kernel_spec_variants(spec):
    rng = np.random.default_rng(spec.rows + spec.cell_bits)
    x = jnp.asarray(rng.integers(0, 1 << spec.input_bits, size=(4, 200)))
    w = jnp.asarray(
        rng.integers(-(1 << (spec.weight_bits - 1)), 1 << (spec.weight_bits - 1), size=(200, 24))
    )
    y_k = ops.crossbar_vmm_op(x, w, spec, interpret=True)
    y_r = ref.crossbar_vmm_ref(x, w, spec)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.slow
@sweep(
    integers(1, 8),
    integers(1, 300),
    integers(1, 40),
    integers(0, 2**32 - 1),
    examples=10,
)
def test_kernel_property(B, K, N, seed):
    rng = np.random.default_rng(seed)
    x, w = _data(rng, B, K, N)
    y_k = ops.crossbar_vmm_op(x, w, SPEC_S, interpret=True)
    y_r = ref.crossbar_vmm_ref(x, w, SPEC_S)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


# ---------------------------------------------------------------------------
# Bit-identity matrix: every Pallas kernel x skip_zero_planes x jit x input
# sparsity vs the dense jnp reference — one grid instead of ad-hoc per-kernel
# coverage (the zero-plane early-out, outer-jit tracing and repaired g_eff
# layouts all ride these same entry points).
# ---------------------------------------------------------------------------

_MB, _MK, _MN = 2, 160, 16  # K=160 pads to two 128-row groups
_MDEV = DeviceConfig(sigma=0.1, p_stuck_on=2e-3, p_stuck_off=2e-3, seed=11)


def _matrix_inputs(case_id: str, sparse: bool):
    rng = np.random.default_rng(zlib.crc32(case_id.encode()))
    if sparse:  # post-ReLU style: mostly zero, codes confined to low planes
        x = rng.integers(0, 1 << 9, size=(_MB, _MK)) * (rng.random((_MB, _MK)) < 0.3)
    else:
        x = rng.integers(0, 1 << 16, size=(_MB, _MK))
    w = rng.integers(-(1 << 15), 1 << 15, size=(_MK, _MN))
    return jnp.asarray(x), jnp.asarray(w)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense_x", "sparse_x"])
@pytest.mark.parametrize("use_jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("skip", [True, False], ids=["skip", "dense_loop"])
@pytest.mark.parametrize("kernel", ["paper", "fast", "noisy"])
def test_kernel_bit_identity_matrix(kernel, skip, use_jit, sparse):
    x, w = _matrix_inputs(f"{kernel}-{sparse}", sparse)
    if kernel == "noisy":
        g = effective_cell_codes(w.astype(jnp.int32) + SPEC_S.weight_bias, SPEC_S, _MDEV)
        fn = functools.partial(
            ops.noisy_vmm_op, spec=SPEC_S, interpret=True, skip_zero_planes=skip
        )
        args = (x, g)
        y_ref = ref.noisy_vmm_ref(x, g, SPEC_S)
    else:
        fn = functools.partial(
            ops.crossbar_vmm_op,
            spec=SPEC_S,
            fast=(kernel == "fast"),
            interpret=True,
            skip_zero_planes=skip,
        )
        args = (x, w)
        y_ref = ref.crossbar_vmm_ref(x, w, SPEC_S)
    if use_jit:
        fn = jax.jit(fn)
    y = fn(*args)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_float_crossbar_matmul_fidelity():
    """The float wrapper approximates x @ w to W16A16 quantization error."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(np.abs(rng.normal(size=(16, 256))).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    y = ops.crossbar_matmul(x, w, interpret=True)
    exact = x @ w
    rel = np.linalg.norm(np.asarray(y - exact)) / np.linalg.norm(np.asarray(exact))
    # 16-bit fixed point with worst-case (static) per-layer output scaling
    assert rel < 5e-3


def test_slstm_scan_kernel_matches_jnp():
    """Fused sLSTM recurrence kernel == the pure-jnp scan (bitwise-close),
    including the carried final state."""
    import jax
    from repro import configs
    from repro.configs.base import reduced
    from repro.models import xlstm as X
    from repro.models.layers import Init
    from repro.kernels.slstm_scan import slstm_scan_pallas

    cfg = reduced(configs.get_config("xlstm-350m"))
    ini = Init(key=jax.random.PRNGKey(0))
    X.init_slstm(ini, cfg)
    params = ini.params
    B, S = 2, 24
    din, H = X.d_inner_of(cfg), cfg.n_heads
    dh = din // H
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.5
    y_ref, _ = X.slstm_block(params, x, cfg, None, decode=False)
    pre = (x @ params["w_in"]).reshape(B, S, 4, H, dh)
    z = jnp.zeros((B, H, dh), jnp.float32)
    h_all, c1, n1, h1 = slstm_scan_pallas(
        pre, params["r_z"], params["r_i"], params["r_f"], params["r_o"],
        z, jnp.ones_like(z), z, interpret=True,
    )
    y_k = h_all.reshape(B, S, din) @ params["out_proj"]
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-5)
    # final state consistent with step-by-step decode
    cache = X.init_xlstm_cache(cfg, "slstm", B)
    for t in range(S):
        _, cache = X.slstm_block(params, x[:, t : t + 1], cfg, cache, decode=True)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(cache["c"]), atol=1e-5)


def test_batched_leading_dims():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 1 << 16, size=(2, 3, 128)))
    w = jnp.asarray(rng.integers(-(1 << 15), 1 << 15, size=(128, 16)))
    y = ops.crossbar_vmm_op(x, w, SPEC_S, interpret=True)
    assert y.shape == (2, 3, 16)
    y_r = ref.crossbar_vmm_ref(x, w, SPEC_S)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_r))
