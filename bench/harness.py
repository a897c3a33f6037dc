"""What every run shares: finding a cell's files by name, building the
served program from a configuration file, the host spans and compile log
the per-layer metrics read, and the device checks.

A configuration file (``bench/configs/<config>.json``) names its registry
entry (``registry``), its plain reference (``reference``), and its sizes.
The served program's ``ModelConfig`` (``program_config``) is the registry
entry, then the published keys the file has (``PUBLISHED``), then the
file's optional ``program`` object: ``ModelConfig`` field names with JSON
values, ``stages`` written as a list of ``{"kinds": [...], "repeats": n,
"moe": [...]}``.  A key of ``program`` that is no field is an error.  A
file that gives no stages is served as ``n_layers`` attention layers.

A reference module (``bench/references/<reference>.py``) imports nothing of
the program and gives these names (``REFERENCE_INTERFACE``):

* ``Dims``, whose ``Dims.from_config(cfg)`` reads the sizes from the file;
* ``init_params(key, dims)``: seeded weights on the device, in the served
  program's parameter tree;
* ``forward(params, tokens, dims, bits, n_valid=None, attn_dtype=None)``:
  logits at every position of ``tokens`` (B, S);
* ``Bits``: the widths of the crossbar datapath, ``Bits()`` the served ones;
* ``decode_kernels(dims, rows)``: ``(name, m, k, n)`` of every crossbar
  kernel call of one decode step over ``rows`` slots, read by
  ``crossbar_vmm_roofline.decode``;
* ``decode_model_flops(dims, contexts)``: the model operations of one
  decode step whose active rows attend over ``contexts`` positions, read
  by ``decode_mfu``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import pathlib
import sys
import time
from typing import Callable, Dict, List, Optional

import jax

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> Dict:
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict  # the workload's entry in BENCHMARK.json
    config: Dict  # bench/configs/<config>.json
    traffic: Dict  # bench/traffic/<traffic>.json
    settings: Dict  # bench/cells/<workload>.json: offered rate, sample, limit
    end_to_end: List[Dict]  # metric entries reported with --trace 0
    per_layer: List[Dict]  # metric entries reported with --trace 1


def _reported_in(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str) -> Cell:
    """Everything a run of ``workload`` needs, found by the names in
    BENCHMARK.json."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = entries[workload]
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    return Cell(
        name=workload,
        entry=entry,
        config=load_json(ROOT / config["file"]),
        traffic=load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        settings=load_json(BENCH / "cells" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reported_in(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reported_in(m, workload)],
    )


def load_reader(metric: str) -> Callable:
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name: str):
    """``bench/references/<name>.py``, the plain reference of a model."""
    path = BENCH / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A PRNG key from any whole number: ``PRNGKey`` keeps only 32 bits."""
    digest = hashlib.sha256(str(int(seed)).encode()).digest()
    hi, lo = int.from_bytes(digest[:4], "little"), int.from_bytes(digest[4:8], "little")
    return jax.random.fold_in(jax.random.PRNGKey(hi >> 1), lo >> 1)


def device_config(cfg: Dict, seed: int):
    """The chip a configuration file names: None for ideal cells, else a
    ``repro.device.DeviceConfig`` of the file's ``chip.device`` settings,
    its draw seeded from the run's seed."""
    chip = cfg["chip"]
    if chip["kind"] == "ideal":
        return None
    from repro.device import DeviceConfig

    return DeviceConfig(**chip["device"], seed=seed % 2**31)


# published key -> (ModelConfig field, type)
PUBLISHED = {
    "num_hidden_layers": ("n_layers", int),
    "hidden_size": ("d_model", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "head_dim": ("head_dim", int),
    "intermediate_size": ("d_ff", int),
    "vocab_size": ("vocab_size", int),
    "rope_theta": ("rope_theta", float),
    "rms_norm_eps": ("norm_eps", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
}


def program_config(cfg: Dict):
    """The served program's ``ModelConfig`` from a configuration file: its
    registry entry, the published keys the file has, then its ``program``
    object (see the module's docstring)."""
    from repro.configs import get_config
    from repro.configs.base import ModelConfig, StageSpec

    fields = {name: kind(cfg[key]) for key, (name, kind) in PUBLISHED.items() if key in cfg}
    if "head_dim" not in cfg and {"hidden_size", "num_attention_heads"} <= set(cfg):
        fields["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    program = dict(cfg.get("program", {}))
    unknown = set(program) - {f.name for f in dataclasses.fields(ModelConfig)}
    if unknown:
        raise KeyError(f"program keys that are no ModelConfig field: {sorted(unknown)}")
    if "stages" in program:
        program["stages"] = tuple(
            StageSpec(kinds=tuple(s["kinds"]), repeats=int(s["repeats"]),
                      moe=tuple(bool(m) for m in s.get("moe", ())))
            for s in program["stages"])
    fields.update(program)
    base = get_config(cfg["registry"])
    if "stages" not in fields:
        fields["stages"] = (StageSpec(kinds=("attn",),
                                      repeats=fields.get("n_layers", base.n_layers)),)
    return dataclasses.replace(base, **fields)


# the names every module under bench/references/ gives (module docstring)
REFERENCE_INTERFACE = ("Dims", "init_params", "forward", "Bits", "decode_kernels",
                       "decode_model_flops")


class CompileLog:
    """Backend compilations (seconds per jitted function, and when each
    ended) and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        self.secs = collections.Counter()
        self.ends: List[float] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs[str(kw.get("fun_name", "?"))] += duration
            self.ends.append(time.perf_counter())

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.ends if t0 <= t < t1)

    def summary(self) -> str:
        steps = {k: round(v, 3) for k, v in self.secs.items()
                 if k in ("jit(decode_step)", "jit(prefill)")}
        other = sum(v for k, v in self.secs.items() if k not in steps)
        return (f"compile seconds {steps}, other {other:.3f}, {len(self.ends)} "
                f"compilations, persistent-cache hits {self.hits}")


class GcLog:
    """The host's garbage collections (start, seconds, generation), from
    ``gc.callbacks``: a full collection of a large heap stalls the
    scheduler's loop."""

    def __init__(self):
        self.records: List = []
        self._t0 = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.records.append((self._t0, time.perf_counter() - self._t0, info["generation"]))

    def close(self) -> None:
        gc.callbacks.remove(self._callback)

    def summary(self, t0: float, t1: float) -> str:
        inside = [(s, g) for t, s, g in self.records if t0 <= t < t1]
        full = [s for s, g in inside if g == 2]
        longest = 1e3 * max((s for s, _ in inside), default=0.0)
        return (f"{len(inside)} garbage collections ({len(full)} full), longest "
                f"{longest:.3f} ms, in all {1e3 * sum(s for s, _ in inside):.3f} ms")


def peak_memory_bytes() -> Optional[int]:
    """The fullest chip's ``peak_bytes_in_use``, where the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class Spans:
    """The benchmark's host spans around its calls into each layer: kept
    in memory as (start, end, meta) per name, on ``time.perf_counter``.
    With ``annotate`` each is also a ``bench.<name>`` span in the profiler's
    trace."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: Dict[str, List] = collections.defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str, **meta):
        ann = (jax.profiler.TraceAnnotation(f"bench.{name}") if self.annotate
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.records[name].append((t0, time.perf_counter(), meta))

    def clear(self) -> None:
        self.records.clear()


def instrument(runner, spans: Spans, slots: Callable[[], list], block_admit: bool) -> None:
    """Wrap the runner's scheduler-facing calls on this instance.  ``slots()``
    gives the current scheduler's slots (None where free), so that a decode
    span records the context of each active row."""
    admit, decode, sample = runner.admit_slot, runner.decode, runner.sample

    def admit_slot(cache, slot, req):
        with spans.span("admit", rid=req.rid, slot=slot, prompt=len(req.prompt)):
            out = admit(cache, slot, req)
            if block_admit:
                jax.block_until_ready(out[0])
        return out

    def decode_(last_tok, pos, cache):
        ctx = [int(pos[i]) + 1 for i, r in enumerate(slots()) if r is not None]
        with spans.span("decode", contexts=ctx):
            return decode(last_tok, pos, cache)

    def sample_(logits):
        with spans.span("sample"):
            return sample(logits)

    runner.admit_slot, runner.decode, runner.sample = admit_slot, decode_, sample_


def device_info() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
