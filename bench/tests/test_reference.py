"""The plain reference: exact code products, and the same model as the
served program where both run digitally."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness

ref = harness.load_reference("llama")


@pytest.mark.parametrize("drop", [17, 26, 28])
@pytest.mark.parametrize("k", [960, 2560, 300])
def test_limb_product_is_exact(k, drop):
    rng = np.random.default_rng(k + drop)
    xq = rng.integers(0, 1 << 16, (8, k))
    wq = rng.integers(-(1 << 15), 1 << 15, (k, 64))
    got = np.asarray(ref.exact_codes_product(jnp.asarray(xq, jnp.int32), jnp.asarray(wq, jnp.int32), drop, 16))
    np.testing.assert_array_equal(got, ref.np_codes_product(xq, wq, drop, 16))


def test_drop_bits_fits_the_worst_case_into_the_window():
    for k in (960, 2560):
        d = ref.drop_bits(k, ref.Bits())
        assert d == 16 + int(np.ceil(np.log2(k)))
        assert ((1 << 16) - 1) * ((1 << 15) - 1) * k < (1 << 15) << d


def test_weights_have_the_served_programs_tree_and_it_runs_them():
    from repro.models import model as model_lib

    cfg = harness.load_json(harness.BENCH / "tests" / "small.json")
    dims = ref.Dims.from_config(cfg)
    pcfg = harness.program_config(cfg)
    params = ref.init_params(harness.seed_key(3), dims)
    want = jax.eval_shape(lambda: model_lib.init_model(jax.random.PRNGKey(0), pcfg,
                                                       dtype=jnp.float32)[0])
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(lambda a: a.shape, want)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, dims.vocab, (2, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = ref.forward(params, toks, dims, None)
        prog = model_lib.forward(params, pcfg, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(prog), rtol=2e-5, atol=2e-5)


def test_padding_rows_leave_the_input_range_alone():
    """A padded call quantizes the valid rows exactly as the unpadded one:
    the input's range leaves the padding out."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(20, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 96)) * 0.05, jnp.float32)
    padded = jnp.concatenate([x, jnp.full((12, 256), 50.0, jnp.float32)])
    valid = jnp.arange(32) < 20
    a = ref.crossbar_linear(x, w, ref.Bits())
    b = ref.crossbar_linear(padded, w, ref.Bits(), valid)[:20]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
