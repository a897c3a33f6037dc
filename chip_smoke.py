"""Smoke run of the served path on TPU: scheduler -> ModelRunner -> programmed
Pallas kernels, at the published widths of smollm-360m.

  python3 chip_smoke.py            # one chip: phases A, B, C
  python3 chip_smoke.py --chips 4  # four chips: expert-parallel mesh serving only

Phases on one chip:

  A  smollm-360m (32 layers, d_model 960, d_ff 2560, vocab 49152, tied head)
     programmed once onto an ideal chip and served through the
     continuous-batching scheduler; one prompt's prefill logits are compared
     with a float32 digital forward of the same params.
  B  the same widths cut to 4 layers, programmed onto a noisy chip
     (variation, stuck cells, spare-column repair), serving the same traffic;
     one prompt's prefill logits are compared with the same forward with the
     noisy kernel replaced by its ``kernels/ref.py`` oracle.
  C  the fast, adaptive and noisy kernels at the full-width shapes, against
     the ``kernels/ref.py`` oracles, bit for bit: on the TPU at every shape,
     and on the host CPU at the first; and a probe that a default-precision
     float32 dot rounds the noisy datapath's operands on the TPU, which is
     why that kernel and its oracle ask for ``Precision.HIGHEST``.

With ``--chips 4`` only the mesh path runs: a registered MoE config shrunk
with ``configs.base.reduced``, programmed onto a noisy chip and served on a
(1, 4) ("data", "model") mesh in the ``ep_only`` layout and on one device;
the tokens must be identical and the expert banks' shards must sit on four
devices.

Weights are random, made from ``--seed``.  Every check that fails raises.
The script exits non-zero, and prints no result, unless JAX's default
backend is a TPU.  Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

MAX_SEQ = 512
MAX_BATCH = 8
N_REQUESTS = 8
MAX_NEW = 16
PROMPT_LENS = (16, 120)
MESH_PROMPT_LENS = (8, 32)  # one prefill bucket: the mesh phase compiles each step once
PROBE_LEN = 64  # a prefill bucket: the probe prompt is served unpadded
# relative L2 error of phase A's prefill logits against the float32 digital
# forward.  The same comparison on the CPU at smollm-360m's widths measured
# 0.147, 0.160 and 0.153 at 2, 4 and 8 layers: the 16-bit output window,
# scaled for the worst-case K-row sum, sets the error, not the depth
LOGITS_REL_ERR_MAX = 0.25
# phase B's forward with the noisy kernel swapped for its oracle: the two
# datapaths give the same codes (phase C), so only XLA's rounding of the
# digital ops around them may differ
NOISY_REF_REL_ERR_MAX = 1e-4
REF_PROBE_LEN = 16  # the oracle's (planes, slices, rows, groups, cols) partials stay small
KERNEL_SHAPES = ((960, 2560), (2560, 960), (960, 49152))
KERNEL_ROWS = (8, 512)
# a noisy chip: variation, stuck cells and spare-column repair
NOISY_DEVICE = dict(sigma=0.05, p_stuck_on=2e-3, p_stuck_off=2e-3, spare_cols=4)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"  ok: {what}", flush=True)


class CompileLog:
    """Backend compile seconds per jitted function, and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        self.secs = collections.Counter()
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs[str(kw.get("fun_name", "?"))] += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self, label: str) -> None:
        steps = {k: round(v, 3) for k, v in self.secs.items()
                 if k in ("jit(decode_step)", "jit(prefill)")}
        other = sum(v for k, v in self.secs.items() if k not in steps)
        print(f"[{label}] compile seconds: {steps}, other {other:.3f}; "
              f"persistent-cache hits {self.hits}", flush=True)
        self.secs.clear()
        self.hits = 0


def peak_memory(label: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[{label}] device peak_bytes_in_use: "
          f"{'not reported' if peak is None else f'{peak / 2**30:.3f} GiB'}", flush=True)


def programmed(cfg, params, label: str, **kw):
    """Program once through the serving entry point; prints the time."""
    from repro.launch.serve import make_runner

    t0 = time.perf_counter()
    runner = make_runner(cfg, params, max_seq=MAX_SEQ, **kw)
    jax.block_until_ready(runner.artifacts)
    print(f"[{label}] programmed {runner.programmed.n_compiled} projections "
          f"in {time.perf_counter() - t0:.2f}s", flush=True)
    return runner


def serve_and_check(runner, prompts, label: str, log: CompileLog):
    """Serve ``prompts`` through the scheduler and check every request."""
    from repro.launch.serve import serve_requests
    from repro.models.layers import crossbar_misses, reset_crossbar_misses

    reset_crossbar_misses()
    t0 = time.perf_counter()
    reqs = serve_requests(runner, prompts, max_new_tokens=MAX_NEW, max_batch=MAX_BATCH)
    print(f"[{label}] served {len(reqs)} requests, "
          f"{sum(len(r.generated) for r in reqs)} tokens in "
          f"{time.perf_counter() - t0:.2f}s (compilation included)", flush=True)
    log.report(label)
    check(len(reqs) == len(prompts) and all(r.done and not r.expired for r in reqs),
          f"all {len(prompts)} requests finished")
    check(all(len(r.generated) == MAX_NEW for r in reqs),
          f"each request generated {MAX_NEW} tokens")
    vocab = runner.cfg.vocab_size
    check(all(0 <= t < vocab for r in reqs for t in r.generated), "tokens within the vocabulary")
    check(crossbar_misses() == (), "zero crossbar misses (strict mode)")
    return reqs


def probe_logits(runner, tokens):
    """Prefill logits of one prompt through the runner, and of a float32
    digital forward of the same params (crossbar off, highest precision)."""
    from repro.models import model as model_lib

    got = runner.prefill(tokens, runner.init_cache(1))[0]
    cfg = runner.cfg
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, t, c: model_lib.prefill(p, cfg, t, c))(
            runner.params, tokens, model_lib.init_cache(cfg, 1, MAX_SEQ, jnp.float32)
        )
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    return got, want, rel


def phase_ideal(cfg, seed: int, log: CompileLog) -> None:
    from repro.launch.serve import init_params, seeded_prompts

    print(f"[A] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, ideal chip", flush=True)
    params, _ = init_params(cfg, seed)
    runner = programmed(cfg, params, "A", seed=seed)
    prompts = seeded_prompts(cfg.vocab_size, N_REQUESTS, *PROMPT_LENS, seed)
    serve_and_check(runner, prompts, "A", log)

    cache = runner.init_cache(MAX_BATCH)
    toks = jnp.zeros((MAX_BATCH, 1), jnp.int32)
    pos = jnp.zeros((MAX_BATCH,), jnp.int32)
    compiled = runner.decode_fn.lower(runner.artifacts, runner.params, toks, pos, cache).compile()
    mem = compiled.memory_analysis()
    print(f"[A] decode step generated_code_size "
          f"{mem.generated_code_size_in_bytes / 1e6:.3f} MB", flush=True)
    check("tpu_custom_call" in compiled.as_text(), "decode step runs the Pallas kernels (tpu_custom_call)")
    del compiled, cache

    probe = seeded_prompts(cfg.vocab_size, 1, PROBE_LEN, PROBE_LEN, seed + 1)[0]
    got, want, rel = probe_logits(runner, jnp.asarray(probe[None]))
    print(f"[A] prefill logits vs float32 digital forward: relative L2 error {rel:.6e}, "
          f"argmax {int(got.argmax())} vs {int(want.argmax())}", flush=True)
    check(bool(np.isfinite(got).all()) and got.shape == (1, cfg.vocab_size),
          f"prefill logits finite, shape (1, {cfg.vocab_size})")
    check(rel <= LOGITS_REL_ERR_MAX, f"relative error {rel:.3e} <= {LOGITS_REL_ERR_MAX}")
    peak_memory("A")
    del runner, params
    gc.collect()


def phase_noisy(cfg, seed: int, log: CompileLog) -> None:
    from repro.device import DeviceConfig
    from repro.launch.serve import init_params, seeded_prompts

    device = DeviceConfig(**NOISY_DEVICE, seed=seed)
    print(f"[B] {cfg.name}: depth cut to {cfg.n_layers} layers at full width (g_eff "
          "holds 32 B per weight: all 32 layers would need ~11.6 GB of HBM); "
          f"noisy chip {device}", flush=True)
    params, _ = init_params(cfg, seed)
    runner = programmed(cfg, params, "B", seed=seed, device=device)
    arts = runner.programmed.by_name
    check(all(a.g_eff is not None for a in arts.values()),
          f"all {len(arts)} artifacts carry device-perturbed cells")
    check(len(runner.repair_reports()) == len(arts),
          "every projection has a spare-column repair report")
    prompts = seeded_prompts(cfg.vocab_size, N_REQUESTS, *PROMPT_LENS, seed)
    serve_and_check(runner, prompts, "B", log)
    probe = seeded_prompts(cfg.vocab_size, 1, PROBE_LEN, PROBE_LEN, seed + 1)[0]
    got, _, rel = probe_logits(runner, jnp.asarray(probe[None]))
    print(f"[B] noisy prefill logits vs float32 digital forward: relative L2 error "
          f"{rel:.6e}", flush=True)
    check(bool(np.isfinite(got).all()), "noisy prefill logits finite")

    probe = jnp.asarray(probe[None, :REF_PROBE_LEN])
    got = np.asarray(runner.prefill(probe, runner.init_cache(1))[0], np.float64)
    want = np.asarray(oracle_prefill(runner, probe), np.float64)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"[B] noisy prefill logits ({REF_PROBE_LEN} tokens) vs the same forward through "
          f"kernels/ref.py's noisy oracle: relative L2 error {rel:.6e}, max abs "
          f"difference {float(np.abs(got - want).max()):.6e}", flush=True)
    check(rel <= NOISY_REF_REL_ERR_MAX, f"relative error {rel:.3e} <= {NOISY_REF_REL_ERR_MAX}")
    check(int(got.argmax()) == int(want.argmax()), "same argmax as the oracle forward")
    peak_memory("B")
    del runner, params, arts
    gc.collect()


def _noisy_oracle(x, g_eff, spec, adc_cfg=None, **_):
    """``kernels/ref.py``'s noisy oracle with ``noisy_vmm_pallas``'s call
    signature (leading dims of ``x`` flattened)."""
    from repro.kernels import ref

    y = ref.noisy_vmm_ref(x.reshape(-1, x.shape[-1]), g_eff, spec, adc_cfg)
    return y.reshape(x.shape[:-1] + y.shape[-1:])


def oracle_prefill(runner, tokens):
    """The runner's prefill logits with every noisy kernel call served by
    the oracle instead, on the same programmed cells."""
    from unittest import mock

    from repro.kernels import noisy_vmm
    from repro.models import model as model_lib

    # a new jitted function, so no trace of the kernel path is reused
    fn = jax.jit(runner._on_chip(model_lib.prefill))
    with mock.patch.object(noisy_vmm, "noisy_vmm_pallas", _noisy_oracle):
        logits = fn(runner.artifacts, runner.params, tokens, runner.init_cache(1))[0]
        return jax.block_until_ready(logits)


def _chunked(ref, x, w, n_axis: int):
    """``ref(x, w)`` over row and column chunks (the datapath is separable in
    both), keeping the oracle's (T, S, M, G, N) partials small."""

    M, N = x.shape[0], w.shape[n_axis]
    mc, nc = min(M, 64), math.gcd(N, 512)
    rows = []
    for i in range(0, M, mc):
        cols = [
            ref(x[i:i + mc], w[..., j:j + nc]) for j in range(0, N, nc)
        ]
        rows.append(jnp.concatenate(cols, axis=-1))
    return jnp.concatenate(rows, axis=0)


def precision_probe(seed: int) -> None:
    """A float32 dot of {0,1} input planes against cells on the 2**-8 grid in
    [0, 3] (10 significant bits), in a Mosaic kernel and in XLA, against the
    exact product: at default precision the TPU rounds the cells, with
    ``Precision.HIGHEST`` it does not."""
    from jax.experimental import pallas as pl

    kp, kg = jax.random.split(jax.random.PRNGKey(seed))
    plane = jax.random.bernoulli(kp, 0.5, (8, 128)).astype(jnp.float32)
    g = jax.random.randint(kg, (128, 128), 0, 3 * 256 + 1).astype(jnp.float32) / 256
    exact = np.asarray(plane, np.float64) @ np.asarray(g, np.float64)

    def mosaic(precision):
        def body(a_ref, b_ref, o_ref):
            o_ref[...] = jax.lax.dot_general(
                a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
        return pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))

    def xla(precision):
        return jax.jit(lambda a, b: jnp.dot(a, b, precision=precision,
                                            preferred_element_type=jnp.float32))

    for name, fn in (("Mosaic", mosaic), ("XLA", xla)):
        n_default = int((np.asarray(fn(None)(plane, g)) != exact).sum())
        n_highest = int((np.asarray(fn(jax.lax.Precision.HIGHEST)(plane, g)) != exact).sum())
        print(f"[C] {name} float32 dot of planes x 2**-8-grid cells: {n_default} of "
              f"{exact.size} outputs inexact at default precision, {n_highest} with "
              "HIGHEST", flush=True)
        check(n_default > 0, f"{name} default-precision dot detected as inexact")
        check(n_highest == 0, f"{name} HIGHEST-precision dot exact")


def phase_kernels(seed: int) -> None:
    from repro.core.adc import SAFE_ADAPTIVE
    from repro.core.crossbar import DEFAULT_SPEC, layer_scaled_spec
    from repro.kernels import ops, ref

    precision_probe(seed)
    cpu = jax.devices("cpu")[0]
    key = jax.random.PRNGKey(seed)
    for K, N in KERNEL_SHAPES:
        spec = layer_scaled_spec(DEFAULT_SPEC, K)
        kx, kw, kg, key = jax.random.split(key, 4)
        wmax = (1 << (spec.weight_bits - 1)) - 1
        w = jax.random.randint(kw, (K, N), -wmax, wmax + 1, jnp.int32)
        # effective cells on the 2**-8 grid in [0, 3], as device.models writes them
        g = jax.random.randint(kg, (spec.n_slices, K, N), 0, 3 * 256 + 1).astype(jnp.float32) / 256
        fast_ref = jax.jit(lambda x, w, spec=spec: ref.crossbar_vmm_ref(x, w, spec))
        adapt_ref = jax.jit(lambda x, w, spec=spec: ref.crossbar_vmm_ref(x, w, spec, SAFE_ADAPTIVE))
        noisy_ref = jax.jit(lambda x, g, spec=spec: ref.noisy_vmm_ref(x, g, spec, SAFE_ADAPTIVE))
        for M in KERNEL_ROWS:
            x = jax.random.randint(kx, (M, K), 0, 1 << spec.input_bits, jnp.int32)
            for name, got, oracle, wt, n_axis in (
                ("fast", ops.crossbar_vmm_op(x, w, spec, fast=True), fast_ref, w, 1),
                ("adaptive", ops.crossbar_vmm_op(x, w, spec, adc_cfg=SAFE_ADAPTIVE),
                 adapt_ref, w, 1),
                ("noisy", ops.noisy_vmm_op(x, g, spec, adc_cfg=SAFE_ADAPTIVE), noisy_ref, g, 2),
            ):
                got = np.asarray(got)
                wants = [("TPU", _chunked(oracle, x, wt, n_axis))]
                if (K, N, M) == (*KERNEL_SHAPES[0], KERNEL_ROWS[0]):
                    wants.append(("host CPU", _chunked(
                        oracle, jax.device_put(x, cpu), jax.device_put(wt, cpu), n_axis)))
                for where, want in wants:
                    n_diff = int((got != np.asarray(want)).sum())
                    print(f"[C] {name} M={M} K={K} N={N}: {n_diff} of {got.size} codes "
                          f"differ from kernels/ref.py on the {where}", flush=True)
                    check(n_diff == 0, f"{name} kernel bit-identical to the oracle on the "
                          f"{where} at M={M} K={K} N={N}")
        del w, g
    peak_memory("C")


def mesh_config():
    """(registered config, its cut) served by the ``--chips 4`` phase."""
    from repro.configs import get_config
    from repro.configs.base import reduced

    base = get_config("kimi-k2-1t-a32b")
    # top-1 routing makes every combine an exact sum (one nonzero term per
    # token), and a capacity factor that drops nothing keeps per-rank
    # capacity from changing which tokens an expert serves
    return base, reduced(base, moe_top_k=1, moe_capacity_factor=1000.0,
                         moe_dispatch="allreduce", layout="ep_only")


def phase_mesh(seed: int, n_dev: int, log: CompileLog) -> None:
    from jax.sharding import Mesh

    from repro.device import DeviceConfig
    from repro.launch.serve import init_params, seeded_prompts

    base, cfg = mesh_config()
    fields = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab_size",
              "moe_experts", "moe_top_k", "moe_d_ff", "moe_shared_experts",
              "moe_capacity_factor", "moe_dispatch", "layout")
    cuts = {f: (getattr(base, f), getattr(cfg, f)) for f in fields
            if getattr(base, f) != getattr(cfg, f)}
    print(f"[mesh] {base.name} cut with configs.base.reduced: {cuts}", flush=True)
    check(cfg.moe_experts % n_dev == 0, f"{cfg.moe_experts} experts divide over {n_dev} devices")
    params, axes = init_params(cfg, seed)
    prompts = seeded_prompts(cfg.vocab_size, N_REQUESTS, *MESH_PROMPT_LENS, seed)

    device = DeviceConfig(**NOISY_DEVICE, seed=seed)
    print(f"[mesh] noisy chip {device}", flush=True)
    single = programmed(cfg, params, "mesh/1 device", seed=seed, device=device)
    want = serve_and_check(single, prompts, "mesh/1 device", log)
    devices = jax.devices()[:n_dev]
    mesh = Mesh(np.array(devices).reshape(1, n_dev), ("data", "model"))
    meshed = programmed(cfg, params, f"mesh/{n_dev} devices", seed=seed,
                        device=device, mesh=mesh, param_axes=axes)
    got = serve_and_check(meshed, prompts, f"mesh/{n_dev} devices", log)
    check([r.generated for r in got] == [r.generated for r in want],
          f"tokens on the {n_dev}-device mesh identical to one device")
    banks = {n: a for n, a in meshed.programmed.by_name.items() if a.w_codes.ndim == 4}
    check(bool(banks), f"expert banks programmed: {sorted(banks)}")
    for name, art in banks.items():
        arr = art.w_codes
        held = {s.device for s in arr.addressable_shards}
        shard_e = {s.data.shape[1] for s in arr.addressable_shards}
        print(f"[mesh] {name}.w_codes {arr.shape}: {len(held)} devices, "
              f"{sorted(shard_e)} experts per shard", flush=True)
        check(len(held) == n_dev and shard_e == {arr.shape[1] // n_dev},
              f"{name}.w_codes split over {n_dev} distinct devices")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: JAX's default backend is {backend!r}, not 'tpu'")
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.configs.base import with_depth
    from repro.launch.serve import enable_compile_cache

    devs = jax.devices()
    print(f"[device] {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {enable_compile_cache()}", flush=True)
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} devices")
    log = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(args.seed, 4, log)
    else:
        cfg = get_config("smollm-360m")
        phase_ideal(cfg, args.seed, log)
        phase_noisy(with_depth(cfg, 4), args.seed, log)
        phase_kernels(args.seed)
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
