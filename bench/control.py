"""Readings that the correctness limits are set from, on the chip at a
cell's own size.  For each seed: the numbers ``correct.py`` compares, of
the served tokens (the program's readings), and the same numbers of the
tokens that each control puts first on the same prompts and tokens (the
controls' readings):

* ``int8``: the reference one precision lower in its datapath (8-bit input
  and weight codes, W8A8) in the program's place;
* ``bf16_attention``: the reference with its KV cache, queries and
  softmax weights in bfloat16, the rest as the configuration states.

The benchmark's own runs do not run it.

  python3 bench/control.py --workloads ideal-decode,ideal-prefill --seeds 1,2,3 --seconds 30,15

For each seed the weights are made and programmed once and every listed
workload of that configuration is served in turn: its pre-roll, then its
``--seconds`` of traffic (one value for all, or one per workload).  One
JSON line per (seed, workload).
"""
import argparse
import gc
import json
import pathlib
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import correct  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import run as bench_run  # noqa: E402


def controls(ref):
    return {"int8": (ref.Bits(8, 8, 16), None), "bf16_attention": (ref.Bits(), jnp.bfloat16)}


def readings(cells, seed: int, seconds):
    spans = harness.Spans()
    gc_log = harness.GcLog()
    runner, params, dims, ref, holder, _ = bench_run.build(cells[0], seed, spans, block_admit=False)
    served = {}
    for cell, secs in zip(cells, seconds):
        bench_run.warm_up(runner, cell, holder, dims.vocab)
        spans.clear()
        preroll = float(cell.traffic["preroll_s"])
        planned = loadgen.plan(cell.traffic, float(cell.settings["rate"]), preroll + secs,
                               dims.vocab, seed)
        rec, sched, w0, w1, _ = bench_run.serve(runner, cell, holder, spans, planned, secs, None)
        finished = bench_run.finished_in(sched, rec, spans, w0, w1)
        off = correct.off_dtype_leaves(sched.kv.cache, cell.config["serving"]["kv_cache_dtype"])
        served[cell.name] = (finished, correct.sample(finished, seed), off,
                             bench_run.window_notes(spans, rec, gc_log, w0, w1))
        holder.sched = None
        del sched
        gc.collect()
    del runner
    gc.collect()
    gc_log.close()
    for cell in cells:
        finished, chosen, off, notes = served[cell.name]
        g = correct.served_gaps(ref, params, dims, chosen, cell.config["serving"]["max_seq"],
                                controls(ref))
        row = {"seed": seed, "workload": cell.name, "finished": len(finished),
               "sampled": len(chosen), "tokens": len(g["gap"]), "kv_cache_off_dtype": off}
        for name in ("gap", *controls(ref)):
            row[name] = {"median": correct.median_gap(g[name]),
                         "widest": float(g[name].max()) if len(g[name]) else None}
        row["window"] = notes
        yield row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workloads", required=True, help="comma-separated, one configuration")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", default="10", help="one value, or one per workload")
    args = ap.parse_args()
    cells = [harness.load_cell(w) for w in args.workloads.split(",")]
    seconds = [float(s) for s in args.seconds.split(",")]
    seconds = seconds * len(cells) if len(seconds) == 1 else seconds
    if len({c.entry["config"] for c in cells}) != 1 or len(seconds) != len(cells):
        raise SystemExit("bench/control.py: the workloads must share one configuration, "
                         "and --seconds give one value or one per workload")
    if jax.default_backend() != "tpu":
        print("bench/control.py: JAX's backend is not a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.launch.serve import enable_compile_cache

    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in readings(cells, seed, seconds):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
