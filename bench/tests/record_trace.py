"""Record the small chip trace that ``test_trace_reduce.py`` reads: two
layers of smollm-360m at published widths on an ideal chip, one
admission and three decode steps over an 8-slot pool, inside a
``bench.window`` span.  Run on the chip from the repository's root:

  python3 bench/tests/record_trace.py
"""
import gzip
import pathlib
import shutil
import sys

import jax
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
import harness  # noqa: E402
import trace_reduce  # noqa: E402

OUT = HERE / "data" / "decode_trace.xplane.pb.gz"


def main():
    if jax.default_backend() != "tpu":
        raise SystemExit("record_trace.py: JAX's backend is not a TPU")
    from repro.launch.serve import make_runner
    from repro.serving.scheduler import ContinuousBatchingScheduler

    cfg = dict(harness.load_json(harness.BENCH / "configs" / "smollm-360m-ideal.json"),
               num_hidden_layers=2)
    ref = harness.load_reference("llama")
    dims = ref.Dims.from_config(cfg)
    params = ref.init_params(harness.seed_key(0), dims)
    runner = make_runner(harness.program_config(cfg), params, max_seq=128)
    spans = harness.Spans(annotate=True)
    sched = ContinuousBatchingScheduler(runner, max_batch=8)
    harness.instrument(runner, spans, lambda: sched.slots, block_admit=True)
    rng = np.random.default_rng(0)
    for _ in range(2):  # compile outside the trace
        sched.submit(rng.integers(0, dims.vocab, 40).astype(np.int32), max_new_tokens=3)
        sched.run()
    sched.submit(rng.integers(0, dims.vocab, 40).astype(np.int32), max_new_tokens=3)
    tdir = str(harness.ROOT / ".bench_trace" / "fixture")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with spans.span("step"):
                sched.step()
    jax.profiler.stop_trace()
    OUT.parent.mkdir(exist_ok=True)
    with open(trace_reduce.find_xplane(tdir), "rb") as f:
        OUT.write_bytes(gzip.compress(f.read(), 9))
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
