"""Serving launcher: program the chip once, serve requests through the scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m [--reduced] \
      --requests 8 --max-new 16

This is the main serving path: ``ModelRunner`` programs every projection
onto crossbars once at construction (``CrossbarMode(enabled=True,
strict=True)``, so a projection without an artifact raises instead of
falling back to per-call programming), and ``ContinuousBatchingScheduler``
admits and decodes the requests.  Without ``--reduced`` the model runs at
its published widths.  ``chip_smoke.py`` at the repo root drives the same
functions on a TPU.

Pallas kernels run in interpret mode off-TPU (``kernels.ops``), so a
published-width run belongs on the chip; ``--reduced`` serves on a CPU.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.configs.base import reduced as reduced_cfg
from repro.models import model as model_lib
from repro.models.layers import CrossbarMode
from repro.serving.engine import ModelRunner, Request
from repro.serving.scheduler import ContinuousBatchingScheduler

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left as it is.  Otherwise the cache lives at ``<repo>/.jax_cache``: a
    fixed path, so the next run of the same program finds its entries.
    Every compile is cached, however short.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def make_runner(
    cfg: ModelConfig,
    params,
    *,
    max_seq: int = 512,
    temperature: float = 0.0,
    seed: int = 0,
    device=None,
    mesh=None,
    param_axes=None,
) -> ModelRunner:
    """Program ``params`` onto crossbars once and return the runner.

    ``device`` (a ``repro.device.DeviceConfig``) selects a noisy chip —
    variation, faults, spare-column repair; None programs ideal cells.
    ``mesh`` + ``param_axes`` place the chip with the weights' shardings.
    """
    return ModelRunner(
        cfg, params, max_seq=max_seq, temperature=temperature, seed=seed,
        crossbar=CrossbarMode(enabled=True, strict=True, device=device),
        mesh=mesh, param_axes=param_axes,
    )


def serve_requests(
    runner: ModelRunner,
    prompts: Sequence[np.ndarray],
    *,
    max_new_tokens: int = 16,
    max_batch: int = 8,
) -> List[Request]:
    """Serve ``prompts`` through a continuous-batching scheduler until all
    finish; returns the requests sorted by rid."""
    sched = ContinuousBatchingScheduler(runner, max_batch=max_batch)
    for p in prompts:
        sched.submit(p, max_new_tokens=max_new_tokens)
    return sched.run()


def seeded_prompts(
    vocab_size: int, n: int, min_len: int, max_len: int, seed: int
) -> List[np.ndarray]:
    """``n`` random token prompts with lengths uniform in [min_len, max_len]."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(min_len, max_len + 1, size=n)
    return [rng.integers(0, vocab_size, size=int(s)).astype(np.int32) for s in lens]


def init_params(cfg: ModelConfig, seed: int):
    """Seeded random float32 params: the crossbar's 16-bit input codes carry
    more precision than bfloat16 activations hold."""
    return model_lib.init_model(jax.random.PRNGKey(seed), cfg, dtype=jnp.float32)


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_cfg(cfg)
    params, _ = init_params(cfg, args.seed)
    t0 = time.perf_counter()
    runner = make_runner(
        cfg, params, max_seq=args.max_seq, temperature=args.temperature,
        seed=args.seed,
    )
    jax.block_until_ready(runner.programmed.artifacts)
    t_prog = time.perf_counter() - t0
    prompts = seeded_prompts(
        cfg.vocab_size, args.requests, 4, min(48, args.max_seq // 2), args.seed
    )
    t0 = time.perf_counter()
    reqs = serve_requests(
        runner, prompts, max_new_tokens=args.max_new, max_batch=args.max_batch
    )
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in reqs)
    print(f"[serve] {cfg.name}: programmed {runner.programmed.n_compiled} "
          f"projections in {t_prog:.2f}s on {jax.devices()[0].device_kind}")
    print(f"[serve] {len(reqs)} requests, {total_tokens} tokens in {dt:.2f}s "
          "(compilation included)")
    for r in reqs[:4]:
        print(f"  req{r.rid}: {r.generated[:12]}")

    from repro.core import arch as hw, energy as en, workloads as wl

    net = wl.lm_workload(cfg)
    newton = en.evaluate(net, hw.NEWTON_CHIP, policy="newton", strassen=True)
    isaac = en.evaluate(net, hw.ISAAC_CHIP, policy="isaac")
    print(f"[newton] analytic energy estimate: {newton.energy_per_sample_j*1e6:.1f} uJ/token "
          f"(ISAAC baseline {isaac.energy_per_sample_j*1e6:.1f} uJ/token, "
          f"{isaac.energy_per_sample_j/newton.energy_per_sample_j:.2f}x)")


if __name__ == "__main__":
    main()
