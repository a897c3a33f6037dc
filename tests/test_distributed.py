"""Distributed correctness on forced multi-host-device CPU backends.

These run in subprocesses (the main test process must keep 1 device for the
smoke tests), each with ``--xla_force_host_platform_device_count=8``:

  * DP+TP sharded loss == single-device loss (same params/batch)
  * shard_map expert-parallel MoE == single-device MoE
  * int8 error-feedback compressed all-reduce: unbiased under error feedback
  * a miniature dry-run (4x2 mesh) exercising the full lower+compile path
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.dist


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_dp_tp_loss_matches_single_device():
    res = _run("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro import configs
        from repro.configs.base import reduced
        from repro.models import model as M
        from repro.models.layers import use_mesh
        from repro.launch import sharding as shlib

        cfg = reduced(configs.get_config("smollm-360m"))
        params, axes = M.init_model(jax.random.PRNGKey(0), cfg)
        key = jax.random.PRNGKey(1)
        batch = {
            "inputs": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
            "targets": jax.random.randint(key, (4, 16), 0, cfg.vocab_size),
        }
        loss_single = float(M.loss_fn(params, cfg, batch))

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        with use_mesh(mesh), mesh:
            p_sh = shlib.param_shardings(
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
                axes, mesh)
            b_sh = shlib.batch_shardings(
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch), mesh)
            p = jax.tree.map(jax.device_put, params, p_sh)
            b = jax.tree.map(jax.device_put, batch, b_sh)
            loss_sharded = float(jax.jit(lambda p, b: M.loss_fn(p, cfg, b))(p, b))
        print(json.dumps({"single": loss_single, "sharded": loss_sharded}))
    """)
    assert abs(res["single"] - res["sharded"]) < 2e-3 * max(1.0, abs(res["single"]))


@pytest.mark.slow
def test_moe_ep_matches_single_device():
    """shard_map EP == single device, once the two *policy* differences are
    held fixed: capacity is per-shard in EP (GShard semantics — uncap it),
    and top-k ties can flip across compiled graphs (separate the logits)."""
    res = _run("""
        import json, dataclasses as dc, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro import configs
        from repro.configs.base import reduced
        from repro.models import moe as Mo
        from repro.models.layers import Init, use_mesh

        cfg = dc.replace(reduced(configs.get_config("deepseek-v2-236b")),
                         moe_capacity_factor=1000.0)
        ini = Init(key=jax.random.PRNGKey(0))
        Mo.init_moe(ini, cfg)
        params = dict(ini.params)
        params["router"] = params["router"] * 100.0  # well-separated logits
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.3
        y_single = Mo.moe_ffn(params, x, cfg)

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
        with use_mesh(mesh), mesh:
            y_ep = jax.jit(lambda p, x: Mo.moe_ffn(p, x, cfg))(params, x)
        diff = float(jnp.max(jnp.abs(y_single - y_ep)))
        rel = diff / (float(jnp.max(jnp.abs(y_single))) + 1e-9)
        print(json.dumps({"rel": rel}))
    """)
    assert res["rel"] < 1e-3


@pytest.mark.slow
def test_compressed_allreduce_error_feedback():
    res = _run("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.train.compression import ef_int8_psum

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))  # per-device rows

        def step(x, err):
            return ef_int8_psum(x, err, "data")

        f = jax.shard_map(step, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False)
        err = jnp.zeros_like(g)
        true_mean = jnp.mean(g, axis=0, keepdims=True)
        # accumulated compressed means over T steps converge to T * true mean
        acc = jnp.zeros((1, 1024))
        T = 20
        for _ in range(T):
            out, err = f(g, err)
            acc = acc + out[:1]
        drift = float(jnp.max(jnp.abs(acc / T - true_mean)))
        scale = float(jnp.max(jnp.abs(true_mean))) + 1e-9
        one, _ = f(g, jnp.zeros_like(g))
        one_err = float(jnp.max(jnp.abs(one[:1] - true_mean)))
        print(json.dumps({"drift_rel": drift / scale, "one_err_rel": one_err / scale}))
    """)
    # single compressed step has visible quantization error; error feedback
    # makes the *average* far more accurate
    assert res["drift_rel"] < res["one_err_rel"]
    assert res["drift_rel"] < 0.02


@pytest.mark.slow
def test_mini_dryrun_both_meshes():
    res = _run("""
        import json, numpy as np, jax, jax.numpy as jnp, dataclasses as dc
        from jax.sharding import Mesh
        from repro import configs
        from repro.configs.base import reduced, SHAPES, ShapeSpec
        from repro.models import model as M
        from repro.models.layers import use_mesh
        from repro.launch import sharding as shlib
        from repro.optim import make_optimizer, constant
        from repro.train import make_train_step

        cfg = reduced(configs.get_config("gemma2-9b"))
        out = {}
        for name, shape_arr in [("pod", (4, 2)), ("multipod", (2, 2, 2))]:
            axes_names = ("data", "model") if len(shape_arr) == 2 else ("pod", "data", "model")
            mesh = Mesh(np.array(jax.devices()).reshape(shape_arr), axes_names)
            with use_mesh(mesh), mesh:
                p_shapes, axes = M.init_model(jax.random.PRNGKey(0), cfg, shape_only=True)
                p_sh = shlib.param_shardings(p_shapes, axes, mesh)
                opt = make_optimizer("adamw", constant(1e-3))
                step = make_train_step(cfg, opt)
                o_shapes = jax.eval_shape(opt.init, p_shapes)
                o_sh = shlib.opt_state_shardings("adamw", o_shapes, p_sh, mesh)
                batch = {
                    "inputs": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                    "targets": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                }
                b_sh = shlib.batch_shardings(batch, mesh)
                c = jax.jit(step, in_shardings=(p_sh, o_sh, None, b_sh)).lower(
                    p_shapes, o_shapes, jax.ShapeDtypeStruct((), jnp.int32), batch
                ).compile()
                out[name] = int(c.memory_analysis().temp_size_in_bytes)
        print(json.dumps(out))
    """)
    assert res["pod"] > 0 and res["multipod"] > 0
