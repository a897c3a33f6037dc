"""``trace_reduce`` and the device-trace readers on a small trace recorded
on a TPU v5e (``record_trace.py``: two layers of smollm-360m at published
widths, one admission and three decode steps over an 8-slot pool)."""
import gzip
import pathlib
import types

import jax
import pytest

import harness
import trace_reduce

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "decode_trace.xplane.pb.gz"
ref = harness.load_reference("llama")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(
        jax.profiler.ProfileData.from_serialized_xspace(gzip.decompress(TRACE.read_bytes())))


@pytest.fixture(scope="module")
def dims():
    cfg = dict(harness.load_json(harness.BENCH / "configs" / "smollm-360m-ideal.json"),
               num_hidden_layers=2)
    return ref.Dims.from_config(cfg)


def test_fixture_is_small():
    assert TRACE.stat().st_size < 1 << 20


def test_window_busy_and_programs(reduced):
    assert reduced.n_chips == 1
    assert 0 < reduced.busy_s <= reduced.window_s
    progs = [p.program for p in reduced.programs]
    assert progs.count("jit_decode_step") == 3
    assert progs.count("jit_prefill") == 1
    assert all(reduced.window[0] <= p.start and p.end <= reduced.window[1]
               for p in reduced.programs)


def test_operations_exclude_containers_and_sum_below_busy(reduced):
    assert not any(o.name.split(".")[0] in trace_reduce.CONTAINERS for o in reduced.ops)
    busy_ops = sum(o.end - o.start for o in reduced.ops) / 1e9
    assert busy_ops > 0.5 * reduced.busy_s
    kernels = [o for o in reduced.ops if o.name.startswith("crossbar_vmm_pallas")]
    per_step = len(ref.decode_kernels(types.SimpleNamespace(
        d_model=960, d_ff=2560, n_heads=15, n_kv_heads=5, head_dim=64, vocab=49152, n_layers=2), 8))
    assert sum(o.program == "jit_decode_step" for o in kernels) == 3 * per_step


def test_gaps_tile_the_idle_time_and_carry_host_labels(reduced):
    idle = sum(b - a for a, b, _ in reduced.gaps) / 1e9
    assert abs(idle - (reduced.window_s - reduced.busy_s)) < 1e-6
    labels = {label for _, _, label in reduced.gaps}
    assert labels <= {"window", "step", "admit", "decode", "sample", "none"}
    b = trace_reduce.breakdown(reduced)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda kv: -kv[1])


def test_device_readers(reduced, dims):
    cfg = {"serving": {"max_batch": 8}}
    decode_calls = [(0.0, 1.0, {"contexts": [41]})] * 3
    ctx = types.SimpleNamespace(
        reduced=reduced, dims=dims, ref=ref, cell=types.SimpleNamespace(config=cfg),
        device_kind="TPU v5 lite", traced=(0.0, 1.0), spans={"decode": decode_calls})
    roof = harness.load_reader("crossbar_vmm_roofline.decode")(ctx)
    mfu = harness.load_reader("decode_mfu")(ctx)
    idle = harness.load_reader("idle_share")(ctx)
    assert 0 < roof <= 100 and 0 < mfu <= 100 and 0 <= idle < 100
    # what the readers read on this trace when the work counts lived in
    # flops.py: taking them from the reference changes no value
    assert (roof, mfu, idle) == (6.023442077631438, 0.01425167410801466, 48.254759336987995)
    ctx.dims = dims._replace(n_layers=3)  # a kernel count that does not match
    assert harness.load_reader("crossbar_vmm_roofline.decode")(ctx) is None
