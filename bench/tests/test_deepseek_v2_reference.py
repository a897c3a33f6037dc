"""The served DeepSeek-V2 program against its plain reference, at a small
size on the CPU: a prompt prefilled into a slot of the pool, then decoded
through the cache token by token, gives the logits the reference's full
forward gives at the same positions; with the reference's weights in 8-bit
codes the same comparison fails."""
import copy

import jax
import numpy as np
import pytest

import harness

FULL = harness.load_json(harness.BENCH / "configs" / "deepseek-v2-lite-ep8-ideal.json")


def small_config():
    """The benchmark's file at CPU widths: one dense and two MoE layers,
    4 of 16 experts held, top-3, YaRN and unnormalized gates as published."""
    cfg = copy.deepcopy(FULL)
    cfg.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, num_hidden_layers=3, n_routed_experts=4,
               num_experts_per_tok=3, vocab_size=512)
    cfg["published"] = {"num_hidden_layers": 3, "n_routed_experts": 16, "vocab_size": 512}
    cfg["program"] = {
        "stages": [{"kinds": ["attn"], "repeats": 1, "moe": [False]},
                   {"kinds": ["attn"], "repeats": 2, "moe": [True]}],
        "kv_lora_rank": 32, "qk_rope_dim": 8, "moe_experts": 16, "moe_top_k": 3,
        "moe_d_ff": 32, "moe_experts_held": 4, "moe_capacity_factor": 16 / 3,
        "crossbar_row_ranges": True,
    }
    cfg["serving"] = dict(cfg["serving"], max_batch=2, max_seq=64)
    return cfg


PROMPT, STEPS = 20, 6

# Relative L2 distance of the served logits from the reference's, over the
# decoded positions.  Each input row is quantized over its own range in
# both, so where both compute a projection from the same input they give
# the same output codes, whatever the batch, the prompt's padding or an
# expert buffer's other rows.  But the 16-bit output window holds a call's
# worst case, so a typical output carries 8 to 11 bits, and a difference of
# one ulp upstream (attention and norms summed in another order) flips an
# output code now and then.  Over three layers the logits move 4.3e-3 to
# 4.8e-3 (the seeds below); with 8-bit weight codes they move 0.16 to 0.18.
REL_TOL = 2e-2


@pytest.fixture(scope="module", params=[2**33 + 5, 7, 8])
def served(request):
    from repro.launch.serve import make_runner
    from repro.serving.engine import Request

    cfg = small_config()
    ref = harness.load_reference(cfg["reference"])
    dims = ref.Dims.from_config(cfg)
    params = ref.init_params(harness.seed_key(request.param), dims)
    runner = make_runner(harness.program_config(cfg), params, max_seq=cfg["serving"]["max_seq"])
    rng = np.random.default_rng(request.param)
    seq = rng.integers(0, dims.vocab, size=PROMPT + STEPS).astype(np.int32)
    cache = runner.init_cache(cfg["serving"]["max_batch"])
    cache, pos, last, _ = runner.admit_slot(cache, 0, Request(0, seq[:PROMPT]))
    toks, at = np.zeros(2, np.int32), np.zeros(2, np.int32)
    out = []
    for i in range(STEPS):
        toks[0], at[0] = last, pos
        logits, cache = runner.decode(toks, at, cache)
        out.append(logits[0])
        last, pos = seq[PROMPT + i], pos + 1
    return cfg, ref, dims, params, seq, np.stack(out)


def _reference_logits(ref, dims, params, seq, bits, length):
    toks = np.zeros((1, length), np.int32)
    n = len(seq) - 1
    toks[0, :n] = seq[:n]
    with jax.default_matmul_precision("highest"):
        logits = ref.forward(params, jax.numpy.asarray(toks), dims, bits, n)[0]
    return np.asarray(logits)[PROMPT - 1:PROMPT - 1 + STEPS]


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_decode_through_the_cache_matches_the_reference(served):
    cfg, ref, dims, params, seq, got = served
    want = _reference_logits(ref, dims, params, seq, ref.Bits(), cfg["serving"]["max_seq"])
    rel = _rel(got, want)
    print("relative L2", rel)
    assert rel < REL_TOL


def test_eight_bit_weight_codes_fail_the_comparison(served):
    cfg, ref, dims, params, seq, got = served
    want = _reference_logits(ref, dims, params, seq, ref.Bits(16, 8, 16),
                             cfg["serving"]["max_seq"])
    rel = _rel(got, want)
    print("relative L2, 8-bit weights", rel)
    assert rel > REL_TOL


def test_decode_kernels_lists_every_crossbar_call_of_a_step():
    cfg = small_config()
    ref = harness.load_reference(cfg["reference"])
    dims = ref.Dims.from_config(cfg)
    calls = ref.decode_kernels(dims, 2)
    # per layer: wq, w_kv_down, 4 + 4 per-head, wo; dense wi/wo; per MoE
    # layer the router, 4 held experts x 3 and 3 shared; the head
    assert len(calls) == 3 * 11 + 2 + 2 * (1 + 12 + 3) + 1
    assert ("expert_wi", 8, 64, 32) in calls and ("w_uk", 2, 16, 32) in calls


def test_column_sums_are_the_programmed_ones():
    """The offset correction's constant: the reference sums each slab's
    columns as the program sums them when it programs the chip, bit for
    bit, so that neither side's rounding of it moves the other's codes."""
    from repro.launch.serve import make_runner

    cfg = small_config()
    ref = harness.load_reference(cfg["reference"])
    dims = ref.Dims.from_config(cfg)
    params = ref.init_params(harness.seed_key(9), dims)
    runner = make_runner(harness.program_config(cfg), params, max_seq=cfg["serving"]["max_seq"])
    sums = ref.with_column_sums(params)
    checked = 0
    for name, art in runner.programmed.by_name.items():
        node = sums
        for key in name.split("/"):
            node = node[key]
        assert np.array_equal(np.asarray(art.w_colsum), np.asarray(node.colsum)), name
        checked += 1
    # the dense stage's 7 projections, the expert stage's 12 and the head
    assert checked == 7 + 12 + 1
