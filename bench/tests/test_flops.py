"""Operation and byte counts against hand-computed values at smollm-360m's
widths (the Llama reference's counts of one decode step, ``flops``'s of
one product), and the table of peaks."""
import math

import pytest

import flops
import harness


ref = harness.load_reference("llama")


@pytest.fixture(scope="module")
def dims():
    return ref.Dims.from_config(harness.load_json(harness.BENCH / "configs" / "smollm-360m-ideal.json"))


def test_programmed_weights(dims):
    # per layer: wq 960x960, wk and wv 960x320, wo 960x960, wi 960x5120,
    # ffn wo 2560x960; then the tied head 960x49152
    per_layer = 921_600 + 2 * 307_200 + 921_600 + 4_915_200 + 2_457_600
    assert per_layer == 9_830_400
    assert ref.programmed_weights(dims) == 32 * per_layer + 47_185_920 == 361_758_720


def test_kernel_cost_of_the_up_projection_at_32_rows():
    f, b = flops.kernel_cost(32, 960, 5120, 2.0)
    assert f == 2 * 32 * 960 * 5120 == 314_572_800
    assert b == 2 * 32 * 960 + 4 * 32 * 5120 + 2 * 960 * 5120 == 10_547_200


def test_roofline_takes_the_larger_bound():
    peak = flops.peaks("TPU v5 lite")
    f, b = flops.kernel_cost(32, 960, 5120, 2.0)
    assert flops.roofline_s(f, b, peak) == b / 819e9  # bandwidth-bound at 32 rows
    f, b = flops.kernel_cost(8192, 960, 5120, 2.0)
    assert flops.roofline_s(f, b, peak) == f / 197e12  # compute-bound at 8192


def test_decode_step_cost_and_model_flops(dims):
    costs = [flops.kernel_cost(32, k, n, 2.0) for _, k, n in ref.projections(dims)]
    f, b = sum(c[0] for c in costs), sum(c[1] for c in costs)
    assert f == 2 * 32 * 361_758_720
    assert b == (2 * 32 * (32 * (4 * 960 + 960 + 2560) + 960)
                 + 4 * 32 * (32 * (960 + 2 * 320 + 960 + 5120 + 960) + 49152)
                 + 2 * 361_758_720)
    attn = 4 * 15 * 64 * 32
    assert math.isclose(ref.decode_model_flops(dims, [10, 20]),
                        2 * 361_758_720 * 2 + attn * 30)


def test_decode_kernels_are_the_projections_over_the_slot_pool(dims):
    calls = ref.decode_kernels(dims, 32)
    assert len(calls) == 6 * 32 + 1
    assert calls == [(name, 32, k, n) for name, k, n in ref.projections(dims)]
    assert calls[4] == ("wi", 32, 960, 5120) and calls[-1] == ("head", 32, 960, 49152)
    assert sum(m * k * n for _, m, k, n in calls) == 32 * 361_758_720


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
