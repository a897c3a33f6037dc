"""The served program's ``ModelConfig`` from a configuration file: the
Llama cells' program as it has been, and intricate architectures from a
``program`` block alone; and the names every reference module gives."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import harness


def test_smollm_file_gives_the_program_it_always_has():
    """Field for field, the config the Llama cells have been served with:
    the registry's smollm-360m entry with the published sizes, the
    published epsilon and 32 attention layers."""
    from repro.configs import get_config
    from repro.configs.base import StageSpec

    cfg = harness.load_json(harness.BENCH / "configs" / "smollm-360m-ideal.json")
    want = dataclasses.replace(
        get_config("smollm-360m"), n_layers=32, d_model=960, n_heads=15, n_kv_heads=5,
        head_dim=64, d_ff=2560, vocab_size=49152, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=True, stages=(StageSpec(kinds=("attn",), repeats=32),))
    got = harness.program_config(cfg)
    assert got == want
    assert {f.name: type(getattr(got, f.name)) for f in dataclasses.fields(got)} == \
        {f.name: type(getattr(want, f.name)) for f in dataclasses.fields(want)}
    assert got.stages[0].moe == (False,)


# a CPU-sized MLA + MoE file on the deepseek-v2 entry: one dense layer,
# then routed experts with shared ones
MLA_MOE = {
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 3, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "registry": "deepseek-v2-236b",
    "program": {
        "stages": [{"kinds": ["attn"], "repeats": 1, "moe": [False]},
                   {"kinds": ["attn"], "repeats": 2, "moe": [True]}],
        "kv_lora_rank": 32, "qk_rope_dim": 8, "moe_experts": 8, "moe_top_k": 2,
        "moe_shared_experts": 2, "moe_d_ff": 48,
    },
}

# a CPU-sized hybrid on the jamba entry: one period of seven Mamba layers
# round one attention layer, experts on every other layer
HYBRID = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 8, "vocab_size": 256,
    "registry": "jamba-v0.1-52b",
    "program": {
        "stages": [{"kinds": ["mamba", "mamba", "mamba", "attn", "mamba", "mamba", "mamba",
                              "mamba"],
                    "repeats": 1, "moe": [False, True] * 4}],
        "moe_experts": 4, "moe_top_k": 2, "moe_d_ff": 128, "mamba_d_inner": 128,
        "mamba_dt_rank": 8,
    },
}


def _init_shapes(pcfg):
    from repro.models import model as model_lib

    return jax.eval_shape(lambda: model_lib.init_model(jax.random.PRNGKey(0), pcfg,
                                                       dtype=jnp.float32)[0])


def test_mla_and_experts_from_a_program_block():
    got = harness.program_config(MLA_MOE)
    assert [s.kinds for s in got.stages] == [("attn",), ("attn",)]
    assert [s.moe for s in got.stages] == [(False,), (True,)]
    assert [got.moe_layer(i) for i in range(3)] == [False, True, True]
    assert (got.kv_lora_rank, got.qk_rope_dim, got.q_lora_rank) == (32, 8, 0)
    assert (got.moe_experts, got.moe_top_k, got.moe_shared_experts, got.moe_d_ff) == (8, 2, 2, 48)
    assert (got.n_layers, got.d_model, got.head_dim, got.d_ff) == (3, 64, 16, 160)
    assert not got.tie_embeddings
    assert jax.tree.leaves(_init_shapes(got))


def test_hybrid_period_from_a_program_block():
    got = harness.program_config(HYBRID)
    assert got.block_pattern_summary() == ["mamba"] * 3 + ["attn"] + ["mamba"] * 4
    assert [got.moe_layer(i) for i in range(8)] == [False, True] * 4
    assert (got.mamba_d_inner, got.mamba_dt_rank, got.moe_experts) == (128, 8, 4)
    assert got.head_dim == 64 // 4  # no head_dim key: hidden over heads
    assert jax.tree.leaves(_init_shapes(got))


def test_a_program_key_that_is_no_field_raises():
    cfg = dict(MLA_MOE, program=dict(MLA_MOE["program"], experts_here=4))
    with pytest.raises(KeyError, match="experts_here"):
        harness.program_config(cfg)


def test_a_head_dim_key_is_taken_over_hidden_over_heads():
    cfg = harness.load_json(harness.BENCH / "tests" / "small.json")
    assert harness.program_config(cfg).head_dim == 256 // 4
    assert harness.program_config(dict(cfg, head_dim=32)).head_dim == 32
    ref = harness.load_reference(cfg["reference"])
    assert ref.Dims.from_config(dict(cfg, head_dim=32)).head_dim == 32


@pytest.mark.parametrize("name", sorted(p.stem for p in (harness.BENCH / "references").glob("*.py")))
def test_every_reference_gives_the_interface(name):
    ref = harness.load_reference(name)
    missing = [n for n in harness.REFERENCE_INTERFACE if not hasattr(ref, n)]
    assert not missing, f"bench/references/{name}.py lacks {missing}"
    assert callable(ref.Dims.from_config)
