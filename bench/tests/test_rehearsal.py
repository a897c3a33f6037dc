"""A CPU rehearsal of whole runs at a small configuration: the harness
without its look for a chip, the timed path sound and then broken
underneath, and the control at a size a test run holds."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correct
import harness
import loadgen
import run as bench_run

# answers long enough for a stale cache to show
TRAFFIC = {"prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 30},
           "output": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
           "block": 8, "warm_prompt_lengths": [30], "preroll_s": 1.0}
# one-token answers after random prompts in one 64-token bucket: at this
# size the model's own greedy continuations keep a margin that int8 codes
# do not close, so the control is read where a random prompt leaves the
# logits close
SHORT = {"prompt": {"median": 32, "sigma": 0.4, "min": 16, "max": 60},
         "output": {"median": 1, "sigma": 0.1, "min": 1, "max": 1},
         "block": 8, "warm_prompt_lengths": [60], "preroll_s": 1.0}
# the limit between the program (0 on seeds 1-3) and the int8 control
# (0.048-0.062 on seeds 1-3) at this size; the faults read 0.099-1.16
CELL = {"limits": {"median_gap": 0.01, "kv_cache_off_dtype": 0}}
SEED = 2**33 + 11  # more than 32 bits, as the benchmark's seeds are


def small_cell(rate=4.0, traffic=TRAFFIC):
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cfg = harness.load_json(harness.BENCH / "tests" / "small.json")
    return harness.Cell("small", {"chips": 1}, cfg, traffic, dict(CELL, rate=rate),
                        bench["end_to_end"], bench["per_layer"])


def test_sound_run_is_correct_and_reports_every_end_to_end_metric():
    res = bench_run.run(small_cell(), SEED, 3.0, False, t_start=time.perf_counter())
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == {"median_gap", "kv_cache_off_dtype"}


def _altered_sample(self, logits):
    """A token altered where it is produced."""
    return (np.argmax(logits, axis=-1).astype(np.int32) + 1) % logits.shape[-1]


def _stale_decode(decode):
    """A step that returns its state unchanged: the cache the decode step
    wrote is dropped."""
    def fn(self, last_tok, pos, cache):
        logits, _ = decode(self, last_tok, pos, cache)
        return logits, cache
    return fn


def _bf16_cache(decode):
    """The KV cache kept one precision lower than the configuration states."""
    def fn(self, last_tok, pos, cache):
        logits, cache = decode(self, last_tok, pos, cache)
        return logits, jax.tree.map(lambda a: a.astype(jnp.bfloat16), cache)
    return fn


@pytest.mark.parametrize("fault", ["altered_token", "state_unchanged", "bf16_cache"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from repro.serving.engine import ModelRunner

    decode = ModelRunner.decode
    if fault == "altered_token":
        monkeypatch.setattr(ModelRunner, "sample", _altered_sample)
    elif fault == "state_unchanged":
        monkeypatch.setattr(ModelRunner, "decode", _stale_decode(decode))
    else:
        monkeypatch.setattr(ModelRunner, "decode", _bf16_cache(decode))
    res = bench_run.run(small_cell(), SEED, 3.0, False, t_start=time.perf_counter())
    print(fault, res["compared"])
    assert not res["correct"], res["compared"]


def test_control_reads_above_the_limit_and_the_program_below():
    """The reference in int8 codes (W8A8) in the program's place: its
    median gap lies above the small configuration's limit, the program's
    below, on three seeds.  The requests are served in one closed batch, so
    that what is compared does not depend on the host's timing."""
    from repro.serving.scheduler import ContinuousBatchingScheduler

    cell = small_cell(8.0, SHORT)
    for seed in (1, 2, 3):
        spans = harness.Spans()
        runner, params, dims, ref, holder, _ = bench_run.build(cell, seed, spans, False)
        sched = ContinuousBatchingScheduler(runner, max_batch=cell.config["serving"]["max_batch"])
        holder.sched = sched
        for p in loadgen.plan(cell.traffic, 8.0, 4.0, dims.vocab, seed)[:32]:
            sched.submit(p.prompt, max_new_tokens=p.max_new)
        served = [(r.prompt, list(r.generated)) for r in sched.run()]
        limits = cell.settings["limits"]
        g = correct.served_gaps(ref, params, dims, served, cell.config["serving"]["max_seq"],
                                {"int8": (ref.Bits(8, 8, 16), None)})
        program, control = correct.median_gap(g["gap"]), correct.median_gap(g["int8"])
        print(seed, program, control)
        assert program <= limits["median_gap"] < control
