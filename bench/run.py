"""Serving benchmark: one cell of BENCHMARK.json on the chip this process
finds, under open-loop traffic, with the served tokens checked against a
plain reference.

  python3 bench/run.py --workload ideal-decode --seed 7 --seconds 30 --trace 0

A run makes the weights on the device from ``--seed``, programs them onto
the emulated crossbar chip through the serving entry point
(``repro.launch.serve.make_runner``), warms up every shape the cell's
traffic uses, offers the traffic's pre-roll, then measures ``--seconds``
of traffic through ``ContinuousBatchingScheduler``.  Every earlier line
of standard output says where the set-up went; the last line is one JSON
object.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics, read from a profiler trace of a few seconds inside
the window and the benchmark's own host spans.

The run exits non-zero, and prints no result, unless JAX's backend is a
TPU with as many chips as the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import correct  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

# the trace covers the last TRACE_S seconds of the window: stopping it
# stalls the host for seconds, which would make later requests late
TRACE_S = 3.0


def log(msg: str) -> None:
    print(msg, flush=True)


def build(cell: harness.Cell, seed: int, spans: harness.Spans, block_admit: bool):
    """Weights from the seed, the programmed runner, its calls wrapped.
    Returns (runner, params, dims, reference module, slots holder, timings)."""
    from repro.launch.serve import make_runner

    ref = harness.load_reference(cell.config["reference"])
    dims = ref.Dims.from_config(cell.config)
    t0 = time.perf_counter()
    params = jax.block_until_ready(ref.init_params(harness.seed_key(seed), dims))
    t_init = time.perf_counter() - t0
    serving = cell.config["serving"]
    t0 = time.perf_counter()
    runner = make_runner(harness.program_config(cell.config), params,
                         max_seq=serving["max_seq"], seed=seed % 2**31,
                         device=harness.device_config(cell.config, seed))
    jax.block_until_ready(runner.artifacts)
    program_s = time.perf_counter() - t0
    holder = types.SimpleNamespace(sched=None)
    harness.instrument(runner, spans, lambda: holder.sched.slots, block_admit)
    return runner, params, dims, ref, holder, {"init_s": t_init, "program_s": program_s}


def warm_up(runner, cell: harness.Cell, holder, vocab: int) -> float:
    """Every shape the cell's traffic uses: one prefill per prompt bucket
    (the traffic file lists a length in each), the slot scatter, the
    decode step over the whole slot pool, sampling."""
    from repro.serving.scheduler import ContinuousBatchingScheduler

    t0 = time.perf_counter()
    sched = ContinuousBatchingScheduler(runner, max_batch=cell.config["serving"]["max_batch"])
    holder.sched = sched
    rng = np.random.default_rng(0)
    for n in cell.traffic["warm_prompt_lengths"]:
        sched.submit(rng.integers(0, vocab, size=n).astype(np.int32), max_new_tokens=2)
    sched.run()
    jax.block_until_ready(sched.kv.cache)
    return time.perf_counter() - t0


def serve(runner, cell, holder, spans, planned, seconds: float, trace_dir: Optional[str]):
    """Pre-roll, then the measured window.  Returns (recorder, scheduler,
    w0, w1, host times of the traced interval or None)."""
    from repro.serving.scheduler import ContinuousBatchingScheduler

    rec = loadgen.Recorder(time.perf_counter())
    sched = ContinuousBatchingScheduler(
        runner, max_batch=cell.config["serving"]["max_batch"], stream=rec.on_token)
    holder.sched = sched
    w0 = float(cell.traffic["preroll_s"])
    w1 = w0 + seconds
    i = loadgen.drive(sched, planned, rec, w0, spans=spans)
    at_open = (len(sched.waiting), sched.n_active)
    traced = None
    if trace_dir is None:
        loadgen.drive(sched, planned, rec, w1, i, spans=spans)
    else:
        i = loadgen.drive(sched, planned, rec, w1 - TRACE_S, i, spans=spans)
        jax.profiler.start_trace(trace_dir)
        t_a = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            loadgen.drive(sched, planned, rec, w1, i, spans=spans)
        t_b = time.perf_counter()
        jax.profiler.stop_trace()
        traced = (t_a, t_b)
    log(f"[window] waiting queue {at_open[0]} at open, {len(sched.waiting)} at close; "
        f"slots in use {at_open[1]} at open, {sched.n_active} at close")
    return rec, sched, w0, w1, traced


def window_notes(spans: harness.Spans, rec, gc_log: harness.GcLog, w0: float, w1: float) -> str:
    """How late the generator ran, the host's garbage collections, and the
    longest scheduler step with the admissions it carried, in the window."""
    t0, t1 = rec.origin + w0, rec.origin + w1
    lag = 1e3 * np.asarray([rec.lag[r] for r, d in rec.due.items() if w0 <= d < w1] or [0.0])
    steps = [(b - a, a, b) for a, b, _ in spans.records.get("step", []) if t0 <= a < t1]
    longest, a, b = max(steps, default=(0.0, 0.0, 0.0))
    admits = sum(1 for s, _, _ in spans.records.get("admit", []) if a <= s < b)
    return (f"generator lag p95 {np.percentile(lag, 95):.3f} ms, max {lag.max():.3f} ms; "
            f"{gc_log.summary(t0, t1)}; longest step {1e3 * longest:.3f} ms with "
            f"{admits} admissions")


def finished_in(sched, rec, spans: harness.Spans, w0: float, w1: float):
    """(prompt, served tokens, slot) of every request whose last token
    reached the host inside the window."""
    slot = {m["rid"]: m["slot"] for _, _, m in spans.records.get("admit", [])}
    return [(r.prompt, list(r.generated), slot[r.rid]) for r in sched.completed.values()
            if r.rid in rec.tokens and w0 <= rec.tokens[r.rid][-1] < w1]


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_start: float = T_START) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    compiles = harness.CompileLog()
    gc_log = harness.GcLog()
    spans = harness.Spans(annotate=trace)
    runner, params, dims, ref, holder, split = build(cell, seed, spans, block_admit=trace)
    log(f"[setup] weights made in {split['init_s']:.3f}s; programmed "
        f"{runner.programmed.n_compiled} projections in {split['program_s']:.3f}s")
    split["warmup_s"] = warm_up(runner, cell, holder, dims.vocab)
    log(f"[setup] warm-up {split['warmup_s']:.3f}s; {compiles.summary()}")
    spans.clear()
    rate = float(cell.settings["rate"])
    preroll = float(cell.traffic["preroll_s"])
    planned = loadgen.plan(cell.traffic, rate, preroll + seconds, dims.vocab, seed)
    trace_dir = None
    if trace:
        trace_dir = str(harness.ROOT / ".bench_trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec, sched, w0, w1, traced = serve(runner, cell, holder, spans, planned, seconds, trace_dir)
    t_open, t_close = rec.origin + w0, rec.origin + w1
    setup_s = t_open - t_start
    in_window = compiles.between(t_open, t_close)
    peak = harness.peak_memory_bytes()
    log(f"[setup] setup_s {setup_s:.3f}: init {split['init_s']:.3f}, program "
        f"{split['program_s']:.3f}, warm-up {split['warmup_s']:.3f}, pre-roll {preroll:.3f}, "
        f"rest {setup_s - split['init_s'] - split['program_s'] - split['warmup_s'] - preroll:.3f}")
    log(f"[window] {len(rec.due)} requests submitted at {rate} req/s; compilations inside "
        f"the window: {in_window}; device peak_bytes_in_use {peak}")
    log(f"[window] {window_notes(spans, rec, gc_log, w0, w1)}")
    e2e = stats.end_to_end(rec.due, rec.tokens, w0, w1)
    log(f"[window] {e2e['n_ttft']} requests due in the window, TTFT p95 {e2e['ttft_p95_ms']:.3f} ms "
        f"(not an end-to-end metric: see PERF.md); {e2e['n_itl']} token gaps")

    ctx = None
    if trace:
        profile = jax.profiler.ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
        reduced = trace_reduce.reduce(profile)
        ctx = types.SimpleNamespace(
            cell=cell, dims=dims, ref=ref, spans=spans.records, rec=rec, w0=w0, w1=w1,
            traced=traced, reduced=reduced, split=split,
            device_kind=jax.devices()[0].device_kind)
        log(f"[trace] {reduced.window_s:.3f}s traced, device busy {reduced.busy_s:.3f}s; "
            f"device seconds per program {reduced.program_seconds()}")

    finished = finished_in(sched, rec, spans, w0, w1)
    off_dtype = correct.off_dtype_leaves(sched.kv.cache, cell.config["serving"]["kv_cache_dtype"])
    attempted = e2e["n_ttft"]
    failed = sum(1 for r in sched.expired.values() if w0 <= rec.due[r.rid] < w1)
    # free the program's state before the reference runs: the device peak
    # above is the program's own
    holder.sched = None
    del sched, runner
    gc.collect()
    chosen = correct.sample(finished, seed)
    t0 = time.perf_counter()
    cmp, gaps = correct.check(ref, params, dims, chosen, cell.config["serving"]["max_seq"],
                              cell.settings["limits"], off_dtype)
    widest = float(gaps["gap"].max()) if len(gaps["gap"]) else None
    log(f"[correct] {len(chosen)} of {len(finished)} requests finished in the window, "
        f"{len(gaps['gap'])} served tokens, "
        f"against the reference in {time.perf_counter() - t0:.3f}s; widest gap {widest}")
    gc_log.close()

    result = {"correct": correct.is_correct(cmp), "attempted": attempted, "failed": failed}
    if not trace:
        values = {"tokens_per_s": e2e["tokens_per_s"], "itl_p95_ms": e2e["itl_p95_ms"],
                  "ttft_p95_ms": e2e["ttft_p95_ms"], "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        result["metrics"] = {}
        for m in cell.per_layer:
            v = harness.load_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    device = harness.device_info()
    device["memory_peak_bytes"] = peak
    if trace:
        device["busy_s"] = ctx.reduced.busy_s
        device["window_s"] = ctx.reduced.window_s
    result["device"] = device
    if trace:
        result["breakdown"] = trace_reduce.breakdown(ctx.reduced)
    result["compared"] = cmp
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = harness.load_cell(args.workload)
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"bench/run.py: JAX's backend is {backend!r}, not 'tpu'", file=sys.stderr)
        return 2
    if len(jax.devices()) < int(cell.entry["chips"]):
        print(f"bench/run.py: the cell asks for {cell.entry['chips']} chips, JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    result = run(cell, args.seed, args.seconds, bool(args.trace))
    for line in correct.describe(result["compared"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
