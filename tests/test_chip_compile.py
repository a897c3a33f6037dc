"""Compiles for a described TPU v5e, with no chip attached.

The crossbar kernels compile with ``interpret=False`` at the full-width
shapes of smollm-360m, and a 2-layer full-width decode step compiles with
the programmed chip passed as an argument.  The decode steps of smollm-360m
and of the DeepSeek-V2-Lite cell write their caches in place, with no
layer of the cache copied.  With the artifacts baked into
the executable as constants, that step's generated code is ~270 MB; as
arguments it is a few MB.  The expert-parallel decode step that
``chip_smoke.py --chips 4`` serves compiles for the four chips of a 2x2
v5e, and a layout that shards dense projections is refused.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  Where it cannot be described, the tests skip.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import with_depth
from repro.core.adc import SAFE_ADAPTIVE
from repro.core.crossbar import DEFAULT_SPEC, layer_scaled_spec
from repro.kernels.crossbar_vmm import crossbar_vmm_pallas
from repro.kernels.noisy_vmm import noisy_vmm_pallas

# smollm-360m's projection shapes (K, N): attention/MLP in, MLP out, tied head
SHAPES = [
    (m, k, n) for k, n in ((960, 2560), (2560, 960), (960, 49152)) for m in (8, 512)
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # keep libtpu's logs out of the temp directory
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile for
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("kernel", ["fast", "adaptive", "noisy"])
@pytest.mark.parametrize("m,k,n", SHAPES)
def test_kernel_compiles_for_v5e(one_chip, kernel, m, k, n):
    spec = layer_scaled_spec(DEFAULT_SPEC, k)
    x = jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip)
    if kernel == "noisy":
        g = jax.ShapeDtypeStruct((spec.n_slices, k, n), jnp.float32, sharding=one_chip)
        lowered = noisy_vmm_pallas.lower(
            x, g, spec=spec, adc_cfg=SAFE_ADAPTIVE, interpret=False
        )
    else:
        w = jax.ShapeDtypeStruct((k, n), jnp.int32, sharding=one_chip)
        kw = dict(fast=True) if kernel == "fast" else dict(adc_cfg=SAFE_ADAPTIVE)
        lowered = crossbar_vmm_pallas.lower(x, w, spec=spec, interpret=False, **kw)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    # profiles name each kernel call after its pallas_call; the benchmark's
    # roofline readers find the kernels by these names
    name = "noisy_vmm_pallas" if kernel == "noisy" else "crossbar_vmm_pallas"
    assert f"%{name}." in text


def _compiled_decode_step(cfg, one_chip, batch, max_seq, monkeypatch):
    """``ModelRunner.decode_fn`` for ``cfg`` with an abstract ideal chip,
    compiled for one described v5e; returns (compiled, abstract cache)."""
    from repro.device.programmed import ProgrammedModel, program_model
    from repro.kernels import ops
    from repro.models import model as model_lib
    from repro.models.layers import CrossbarMode
    from repro.serving.engine import ModelRunner

    # the backend here is the CPU; the step is compiled for the TPU
    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    params = model_lib.init_model(
        jax.random.PRNGKey(0), cfg, dtype=jnp.float32, shape_only=True
    )[0]
    arts = jax.eval_shape(
        lambda p: program_model(p, tie_lm_head=cfg.tie_embeddings).artifacts, params
    )
    runner = ModelRunner(
        cfg, params, max_seq=max_seq, verify_coverage=False,
        crossbar=CrossbarMode(enabled=True, strict=True, programmed=ProgrammedModel(arts)),
    )
    cache = jax.eval_shape(lambda: runner.init_cache(batch))
    compiled = runner.decode_fn.lower(
        _on(one_chip, arts),
        _on(one_chip, params),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
        _on(one_chip, cache),
    ).compile()
    return compiled, cache


def test_full_width_decode_step_takes_chip_as_argument(one_chip, monkeypatch):
    cfg = with_depth(get_config("smollm-360m"), 2)
    compiled, _ = _compiled_decode_step(cfg, one_chip, 8, 512, monkeypatch)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%crossbar_vmm_pallas." in text
    assert compiled.memory_analysis().generated_code_size_in_bytes < 64 * 2**20


_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\([^=]*?\)|\w+\[[\d,]*\]\{[^}]*\}) ([\w-]+)\((.*)")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")
# what a decode step may do with an array the size of a layer's cache
# without copying it: pass it on, view it, or write a token into it
_PASS = {"parameter", "get-tuple-element", "bitcast", "tuple"}


def _cache_sized_copies(text: str, batch: int, seq: int, min_elems: int, staged=()):
    """Instructions of the step's unfused computations (the entry, the layer
    scan's body) that yield a fresh array the size of a layer's cache: one
    whose dims hold ``batch`` and ``seq`` and at least ``min_elems``
    elements.  A dynamic-update-slice whose update is smaller writes in
    place.  An asynchronous copy of an array whose dims are one of
    ``staged``, in its own layout into or out of another memory space, is
    memory-space assignment staging that array in on-chip memory."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.-]+)", text))
    arrays, found, comp = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, typ, op, rest = m.groups()
        shapes = _ARRAY.findall(typ)
        arrays[name] = shapes
        if comp in fused or op in _PASS:
            continue
        dims = [int(d) for d in shapes[0][1].split(",") if d] if shapes else []
        if not (batch in dims and seq in dims and np.prod(dims) >= min_elems):
            continue
        if op == "dynamic-update-slice":
            update = arrays[re.findall(r"%([\w.-]+)", rest)[1]][0]
            if np.prod([int(d) for d in update[1].split(",") if d]) < min_elems:
                continue
        if op in ("copy-start", "copy-done") and tuple(dims) in staged:
            src = arrays[re.findall(r"%([\w.-]+)", rest)[0]][0]
            if src[:2] == shapes[0][:2] and src[2].split(":")[0] == shapes[0][2].split(":")[0]:
                continue
        found.append(f"{comp}: {name} = {op} {typ}")
    return found


def _mla_three_layers():
    """deepseek-v2-lite-ep8-ideal's served program (published widths, eight
    held experts, a 12,800-row vocabulary slice) cut to its dense layer and
    two MoE layers, so that the MoE stage runs as a loop."""
    import dataclasses

    from repro.configs.base import StageSpec

    return dataclasses.replace(
        get_config("deepseek-v2-lite"), n_layers=3, vocab_size=12800,
        stages=(StageSpec(kinds=("attn",), repeats=1, moe=(False,)),
                StageSpec(kinds=("attn",), repeats=2, moe=(True,))),
        moe_experts_held=8, moe_capacity_factor=64 / 6, crossbar_row_ranges=True)


@pytest.mark.parametrize("which,batch,max_seq,staged", [
    pytest.param("smollm-360m", 32, 1024, (), id="smollm-360m-32-1024"),
    # the dense stage's one-layer rope-key stack (64 MiB) is the one array
    # of the cache that memory-space assignment stages on chip
    pytest.param("deepseek-v2-lite-ep8-ideal", 32, 8192, ((1, 32, 8192, 1, 64),),
                 id="deepseek-v2-lite-ep8-ideal-32-8192"),
])
def test_decode_step_writes_the_cache_in_place(one_chip, monkeypatch, which, batch, max_seq,
                                               staged):
    """The decode step keeps each stage's cache in one donated buffer: no
    layer of it is copied, relaid out, sliced out, scattered into a fresh
    buffer or restacked, and the whole cache aliases the step's output."""
    if which == "smollm-360m":
        cfg = with_depth(get_config(which), 2)
    else:
        cfg = _mla_three_layers()
    compiled, cache = _compiled_decode_step(cfg, one_chip, batch, max_seq, monkeypatch)
    leaves = jax.tree.leaves(cache)
    layer = min(int(np.prod(a.shape[1:])) for a in leaves)
    assert layer >= batch * max_seq * 64
    found = _cache_sized_copies(compiled.as_text(), batch, max_seq, layer, staged)
    assert not found, "\n".join(found)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


def _mesh_runner(mesh, layout, monkeypatch):
    """A runner for ``chip_smoke.py --chips 4``'s config, on ``mesh``, with
    an abstract ideal chip; returns (runner, decode-step arguments)."""
    import dataclasses

    from chip_smoke import mesh_config
    from repro.device.programmed import ProgrammedModel, program_model
    from repro.kernels import ops
    from repro.models import model as model_lib
    from repro.models.layers import CrossbarMode
    from repro.serving.engine import ModelRunner

    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    cfg = dataclasses.replace(mesh_config()[1], layout=layout)
    params = model_lib.init_model(
        jax.random.PRNGKey(0), cfg, dtype=jnp.float32, shape_only=True
    )[0]
    arts = jax.eval_shape(
        lambda p: program_model(p, tie_lm_head=cfg.tie_embeddings).artifacts, params
    )
    runner = ModelRunner(
        cfg, params, max_seq=64, verify_coverage=False, mesh=mesh,
        crossbar=CrossbarMode(enabled=True, strict=True, programmed=ProgrammedModel(arts)),
    )
    rep = NamedSharding(mesh, P())
    batch = 8
    args = (
        _on(rep, arts),
        _on(rep, params),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=rep),
        _on(rep, jax.eval_shape(lambda: runner.init_cache(batch))),
    )
    return runner, args


def test_ep_only_decode_step_compiles_for_four_chips(four_chips, monkeypatch):
    runner, args = _mesh_runner(four_chips, "ep_only", monkeypatch)
    compiled = runner.decode_fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_dense_layout_refuses_replicated_kernels(four_chips, monkeypatch):
    runner, args = _mesh_runner(four_chips, "tp", monkeypatch)
    with pytest.raises(ValueError, match="ep_only"):
        runner.decode_fn.lower(*args)
