"""Knee sweep of one cell, in one process on the chip: one open-loop
stream whose offered rate steps up, and for each step the waiting queue at
its start and end, the tokens per second completed, and the tails.  The
knee is the highest offered rate at which the waiting queue does not grow
over the step.

  python3 bench/sweep.py --workload ideal-decode --rates 1,1.5,2,2.5 --step-seconds 20

Prints one JSON line per step, then one with the knee, and writes them to
``--out`` when given.  The cell's rate (``bench/cells/<workload>.json``) is
then set to about four fifths of the knee.
"""
import argparse
import json
import pathlib
import sys
import time

import jax

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import harness  # noqa: E402
import loadgen  # noqa: E402
import run as bench_run  # noqa: E402
import stats  # noqa: E402

GROWTH_TOLERANCE = 1  # requests: a queue that ends this much longer has grown


def sweep(cell: harness.Cell, rates, step_s: float, seed: int):
    from repro.serving.scheduler import ContinuousBatchingScheduler

    spans = harness.Spans()
    runner, _, dims, _, holder, split = bench_run.build(cell, seed, spans, block_admit=False)
    bench_run.warm_up(runner, cell, holder, dims.vocab)
    rec = loadgen.Recorder(time.perf_counter())
    sched = ContinuousBatchingScheduler(
        runner, max_batch=cell.config["serving"]["max_batch"], stream=rec.on_token)
    holder.sched = sched
    steps, t = [], 0.0
    for k, rate in enumerate(rates):
        planned = [loadgen.Planned(p.due + t, p.prompt, p.max_new)
                   for p in loadgen.plan(cell.traffic, rate, step_s, dims.vocab, seed + k)
                   if p.due < step_s]
        start_q = len(sched.waiting)
        loadgen.drive(sched, planned, rec, t + step_s)
        e2e = stats.end_to_end(
            {r: d for r, d in rec.due.items() if t <= d < t + step_s}, rec.tokens, t, t + step_s)
        row = {"rate": rate, "offered": len(planned) / step_s, "waiting_start": start_q,
               "waiting_end": len(sched.waiting), "active_end": sched.n_active, **e2e}
        row["grows"] = row["waiting_end"] > start_q + GROWTH_TOLERANCE
        print(json.dumps(row), flush=True)
        steps.append(row)
        t += step_s
    ok = [s["rate"] for s in steps if not s["grows"]]
    return steps, (max(ok) if ok else None), split


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s, ascending")
    ap.add_argument("--step-seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    if jax.default_backend() != "tpu":
        print("bench/sweep.py: JAX's backend is not a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    steps, knee, split = sweep(cell, rates, args.step_seconds, args.seed)
    out = {"workload": args.workload, "knee": knee, "step_seconds": args.step_seconds,
           "device": harness.device_info(), "program_s": split["program_s"], "steps": steps}
    print(json.dumps({"knee": knee}), flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
