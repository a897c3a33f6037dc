"""Compiles for a described TPU v5e, with no chip attached.

The crossbar kernels compile with ``interpret=False`` at the full-width
shapes of smollm-360m, and a 2-layer full-width decode step compiles with
the programmed chip passed as an argument.  With the artifacts baked into
the executable as constants, that step's generated code is ~270 MB; as
arguments it is a few MB.  The expert-parallel decode step that
``chip_smoke.py --chips 4`` serves compiles for the four chips of a 2x2
v5e, and a layout that shards dense projections is refused.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.  Where it cannot be described, the tests skip.
"""
from __future__ import annotations

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


def test_full_width_decode_step_takes_chip_as_argument(one_chip, monkeypatch):
    from repro.device.programmed import ProgrammedModel, program_model
    from repro.kernels import ops
    from repro.models import model as model_lib
    from repro.models.layers import CrossbarMode
    from repro.serving.engine import ModelRunner

    # the backend here is the CPU; the step is compiled for the TPU
    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    cfg = with_depth(get_config("smollm-360m"), 2)
    params = model_lib.init_model(
        jax.random.PRNGKey(0), cfg, dtype=jnp.float32, shape_only=True
    )[0]
    arts = jax.eval_shape(lambda p: program_model(p, tie_lm_head=True).artifacts, params)
    runner = ModelRunner(
        cfg, params, max_seq=512, verify_coverage=False,
        crossbar=CrossbarMode(enabled=True, strict=True, programmed=ProgrammedModel(arts)),
    )
    batch = 8
    compiled = runner.decode_fn.lower(
        _on(one_chip, arts),
        _on(one_chip, params),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip),
        _on(one_chip, jax.eval_shape(lambda: runner.init_cache(batch))),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%crossbar_vmm_pallas." in text
    assert compiled.memory_analysis().generated_code_size_in_bytes < 64 * 2**20


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
