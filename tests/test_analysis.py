"""Static contract checker (ISSUE 9): lint rules + offline store verifier.

Each lint rule class is pinned by a fixture true positive (a crafted
snippet that must produce exactly the expected finding) and the whole repo
is pinned clean: ``run_lint()`` over the live tree yields zero error-level
findings, so the CI gate (``python -m repro.analysis --check``) is green by
construction and any regression is a visible diff in these tests.

``verify_store`` is exercised against real ``save_programmed`` stores: a
freshly programmed (planned, device-noised) chip verifies OK from manifest
and npz headers alone, and the three corruption classes the issue names —
bad name-set, dangling ACTIVE pointer, over-budget plan — are each
rejected with the right rule.  Tolerant decode is regression-pinned:
manifests predating the planner/lifecycle (no ``plan`` / ``device`` /
``t_service_s``) still restore and still verify.
"""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ALL_RULES, ERROR, INFO, lint_source, run_lint, verify_store
from repro.analysis.rules_determinism import rule_barrier, rule_rng
from repro.analysis.rules_device import rule_shadowing, rule_stage_keys
from repro.analysis.rules_matmul import rule_digital_fallback
from repro.analysis.rules_pallas import rule_pallas
from repro.checkpoint import restore_programmed, save_programmed, swap_active
from repro.core.planner import plan_model
from repro.device import DeviceConfig, program_model
from repro.device.programmed import expected_artifact_names

DEV = DeviceConfig(sigma=0.1, p_stuck_on=1e-3, p_stuck_off=1e-3, write_verify_iters=4)


def _params(seed=0, K=32, N=8):
    rng = np.random.default_rng(seed)
    return {"wq": jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))}


def _saved_store(tmp_path, params, *, planned=True, slot=None):
    plan = plan_model(params, device=DEV) if planned else None
    prog = program_model(params, device=DEV, plan=plan)
    save_programmed(str(tmp_path), prog, slot=slot)
    return prog


def _manifest_path(tmp_path, slot=None):
    sub = f"programmed.slot{slot}" if slot else "programmed"
    return os.path.join(str(tmp_path), sub, "manifest.json")


def _edit_manifest(tmp_path, fn, slot=None):
    path = _manifest_path(tmp_path, slot)
    with open(path) as f:
        manifest = json.load(f)
    fn(manifest)
    with open(path, "w") as f:
        json.dump(manifest, f)


# ---------------------------------------------------------------------------
# lint rules: one fixture true positive per rule class
# ---------------------------------------------------------------------------

def test_rule_registry_has_all_six_classes():
    names = {r.__name__ for r in ALL_RULES}
    assert names == {
        "rule_digital_fallback", "rule_rng", "rule_barrier",
        "rule_stage_keys", "rule_shadowing", "rule_pallas",
    }


def test_digital_fallback_flags_unclassified_matmul():
    src = "def f(x, params):\n    return x @ params['w_new']\n"
    fs = lint_source("src/repro/models/newmodel.py", src,
                     rules=[rule_digital_fallback])
    assert len(fs) == 1
    assert fs[0].rule == "digital-fallback" and fs[0].level == ERROR
    assert "unclassified matmul" in fs[0].message
    # out of scope: the same site elsewhere is not this rule's business
    assert lint_source("src/repro/serving/x.py", src,
                       rules=[rule_digital_fallback]) == []


def test_digital_fallback_audit_statuses(monkeypatch):
    import repro.analysis.rules_matmul as rm
    monkeypatch.setitem(rm.AUDIT, "src/repro/models/fake.py", {
        "x @ w": ("known", "not lifted yet"),
        "q @ w": ("allow", "weightless"),
        "gone @ w": ("allow", "site was deleted"),
    })
    fs = lint_source("src/repro/models/fake.py", "a = x @ w\nb = q @ w\n",
                     rules=[rm.rule_digital_fallback])
    # known -> info (visible, non-fatal); allow -> silent; stale -> error
    levels = sorted((f.level, f.message.split(":")[0]) for f in fs)
    assert levels == [
        (ERROR, "stale AUDIT entry (site no longer in file)"),
        (INFO, "known-digital projection"),
    ]


def test_rng_rule_flags_unseeded_and_wall_clock():
    src = (
        "import time, jax\nimport numpy as np\n"
        "k = jax.random.PRNGKey(epoch)\n"          # seed from a step counter
        "g = np.random.default_rng()\n"            # argless generator
        "v = np.random.normal(0.0, 1.0)\n"         # hidden global state
        "t = time.time()\n"                        # wall clock in src/
    )
    fs = lint_source("src/repro/serving/fake.py", src, rules=[rule_rng])
    assert len(fs) == 4 and all(f.rule == "determinism-rng" for f in fs)
    clean = (
        "import jax\nimport numpy as np\n"
        "k = jax.random.PRNGKey(0)\n"
        "k2 = jax.random.PRNGKey(cfg.seed + 1)\n"
        "g = np.random.default_rng(seed)\n"
    )
    assert lint_source("src/repro/serving/fake.py", clean, rules=[rule_rng]) == []
    # wall clock outside src/ (benchmark timing loops) is not a finding
    assert lint_source("benchmarks/fake.py", "import time\nt = time.time()\n",
                       rules=[rule_rng]) == []


@pytest.mark.parametrize("read", [
    "time.perf_counter()", "time.perf_counter_ns()", "time.monotonic()", "time.time_ns()",
    "perf_counter()",
])
def test_rng_rule_flags_every_host_clock_in_src(read):
    imports = "from time import perf_counter\n" if read == "perf_counter()" else "import time\n"
    src = imports + f"t = {read}\n"
    fs = lint_source("src/repro/serving/fake.py", src, rules=[rule_rng])
    assert len(fs) == 1 and fs[0].rule == "determinism-rng"
    # the allowlisted reporting sites and code outside src/ read clocks freely
    for relpath in ("src/repro/train/loop.py", "src/repro/launch/serve.py", "bench/fake.py"):
        assert lint_source(relpath, src, rules=[rule_rng]) == []


def test_barrier_rule_flags_unpinned_two_scale_product():
    bad = "def f(x, x_scale, w_scale):\n    return x * (x_scale * w_scale)\n"
    fs = lint_source("src/repro/device/fake.py", bad, rules=[rule_barrier])
    assert len(fs) == 1 and fs[0].rule == "determinism-barrier"
    assert "optimization_barrier" in fs[0].message
    pinned = (
        "def f(x, x_scale, w_scale):\n"
        "    return x * jax.lax.optimization_barrier(x_scale * w_scale)\n"
    )
    assert lint_source("src/repro/device/fake.py", pinned, rules=[rule_barrier]) == []
    # same-scale grid snap (round(c*scale)/scale) is not the hazard
    snap = "def q(c, scale):\n    return jnp.round(c * scale) / scale\n"
    assert lint_source("src/repro/device/fake.py", snap, rules=[rule_barrier]) == []
    # the device family is the scope; models/ scale math is out of scope
    assert lint_source("src/repro/models/fake.py", bad, rules=[rule_barrier]) == []


def test_stage_rule_flags_registry_index_collision():
    src = (
        "STAGE_A = 'faults'\nSTAGE_B = 'program'\n"
        "_STAGES = {STAGE_A: 0, STAGE_B: 0}\n"
    )
    fs = lint_source("src/repro/device/models.py", src, rules=[rule_stage_keys])
    assert any("index collision" in f.message for f in fs)
    ok = (
        "STAGE_A = 'faults'\nSTAGE_B = 'program'\n"
        "_STAGES = {STAGE_A: 0, STAGE_B: 1}\n"
    )
    assert lint_source("src/repro/device/models.py", ok, rules=[rule_stage_keys]) == []


def test_stage_rule_flags_ad_hoc_literals_and_duplicate_fold_in():
    src = (
        "def f(cfg, shape, tag, key):\n"
        "    m = fault_masks(cfg, shape, tag, stage='faults')\n"
        "    k = _stage_key(cfg, 'program', tag)\n"
        "    k1 = jax.random.fold_in(key, 3)\n"
        "    k2 = jax.random.fold_in(key, 3)\n"
    )
    fs = lint_source("src/repro/device/fake.py", src, rules=[rule_stage_keys])
    msgs = "\n".join(f.message for f in fs)
    assert len(fs) == 3
    assert "stage='faults'" in msgs and "'program'" in msgs
    assert "fold_in index literal 3" in msgs


def test_real_stage_registry_is_collision_free():
    import repro.device.models as dm
    assert len(set(dm._STAGES.values())) == len(dm._STAGES)
    assert set(dm._STAGES) == {
        dm.STAGE_FAULTS, dm.STAGE_PROGRAM,
        dm.STAGE_SPARE_FAULTS, dm.STAGE_SPARE_PROGRAM,
    }


def test_shadowing_rule_flags_aux_slot_rebind():
    # the PR 7 bug, verbatim shape: a RepairPlan local named `plan`
    src = (
        "def fix_layer(g_eff, spare):\n"
        "    plan = plan_repair(g_eff, spare)\n"
        "    return apply_repair(g_eff, plan)\n"
    )
    fs = lint_source("src/repro/device/repair.py", src, rules=[rule_shadowing])
    assert len(fs) == 1 and fs[0].rule == "aux-slot-shadowing"
    assert "PR 7" in fs[0].message
    # the audited allowlist admits the canonical sites
    allowed = (
        "def repaired_effective_cells(g, cfg):\n"
        "    report = build_report(g)\n"
        "    return g, report\n"
    )
    assert lint_source("src/repro/device/repair.py", allowed,
                       rules=[rule_shadowing]) == []
    # non-slot names are never flagged
    renamed = src.replace("plan", "rplan")
    assert lint_source("src/repro/device/repair.py", renamed,
                       rules=[rule_shadowing]) == []


def test_pallas_rule_flags_side_effects_and_trace_time_branch():
    src = (
        "def k(x_ref, o_ref):\n"
        "    print('step')\n"
        "    if pl.program_id(0) == 0:\n"
        "        o_ref[...] = x_ref[...]\n"
    )
    fs = lint_source("src/repro/kernels/fake.py", src, rules=[rule_pallas])
    msgs = "\n".join(f.message for f in fs)
    assert len(fs) == 2
    assert "side effect" in msgs and "@pl.when" in msgs


def test_pallas_rule_flags_blockspec_grid_arity_mismatch():
    src = (
        "def launch(x):\n"
        "    return pl.pallas_call(\n"
        "        k, grid=(4, 4),\n"
        "        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],\n"
        "    )(x)\n"
    )
    fs = lint_source("src/repro/kernels/fake.py", src, rules=[rule_pallas])
    assert len(fs) == 1
    assert "1 arg(s)" in fs[0].message and "2 dimension(s)" in fs[0].message
    ok = src.replace("lambda i:", "lambda i, j:")
    assert lint_source("src/repro/kernels/fake.py", ok, rules=[rule_pallas]) == []


def test_repo_is_lint_clean():
    """The CI gate's invariant: the live tree carries zero error-level
    findings, and the known-digital map (info findings) is non-empty — the
    not-yet-lifted projections stay visible instead of becoming folklore."""
    findings = run_lint()
    errors = [f for f in findings if f.level == ERROR]
    assert errors == [], "\n".join(f.format() for f in errors)
    assert any(f.level == INFO and f.rule == "digital-fallback" for f in findings)


# ---------------------------------------------------------------------------
# offline store verification
# ---------------------------------------------------------------------------

def test_verify_store_accepts_fresh_planned_store(tmp_path):
    params = _params()
    _saved_store(tmp_path, params)
    rep = verify_store(str(tmp_path), expected=expected_artifact_names(params))
    assert rep.ok, rep.summary()
    assert rep.n_artifacts == 1
    assert "OK" in rep.summary()


def test_verify_store_follows_active_slot(tmp_path):
    params = _params()
    _saved_store(tmp_path, params, slot="A")
    swap_active(str(tmp_path), "A")
    rep = verify_store(str(tmp_path), expected=expected_artifact_names(params))
    assert rep.ok, rep.summary()
    assert rep.slot == "A"


def test_verify_store_rejects_wrong_model_name_set(tmp_path):
    _saved_store(tmp_path, _params(), planned=False)
    rep = verify_store(str(tmp_path), expected={"wk": (32, 8)})
    assert not rep.ok
    assert {f.rule for f in rep.findings} == {"name-set"}
    msgs = "\n".join(f.format() for f in rep.findings)
    # both directions: the missing expected name and the orphaned store leaf
    assert "[wk]" in msgs and "silently fall back" in msgs
    assert "[wq]" in msgs and "orphaned leaf" in msgs


def test_verify_store_rejects_dangling_active_pointer(tmp_path):
    (tmp_path / "programmed.ACTIVE").write_text("A")
    rep = verify_store(str(tmp_path))
    assert not rep.ok
    assert rep.findings[0].rule == "active-pointer"
    assert "dangling ACTIVE pointer" in rep.findings[0].message


def test_verify_store_rejects_corrupt_active_pointer(tmp_path):
    (tmp_path / "programmed.ACTIVE").write_text("Z")
    rep = verify_store(str(tmp_path))
    assert not rep.ok
    assert rep.findings[0].rule == "active-pointer"
    assert "corrupt" in rep.findings[0].message


def test_verify_store_rejects_over_budget_plan(tmp_path):
    params = _params()
    _saved_store(tmp_path, params)
    # sanity: the plan is admissible without a budget...
    assert verify_store(str(tmp_path)).ok
    # ...and over budget under an impossible one (every datapath needs
    # crossbar area; 0.1x admits nothing)
    rep = verify_store(str(tmp_path), max_crossbar_factor=0.1)
    assert not rep.ok
    assert any(f.rule == "plan" and "over budget" in f.message
               for f in rep.findings)


def test_verify_store_rejects_undecodable_plan(tmp_path):
    _saved_store(tmp_path, _params())

    def corrupt(manifest):
        manifest["artifacts"]["wq"]["plan"]["datapath"] = "quantum"

    _edit_manifest(tmp_path, corrupt)
    rep = verify_store(str(tmp_path))
    assert not rep.ok
    assert any(f.rule == "plan" and "inadmissible plan" in f.message
               for f in rep.findings)


def test_verify_store_rejects_missing_npz_and_unknown_schema(tmp_path):
    _saved_store(tmp_path, _params(), planned=False)

    def corrupt(manifest):
        manifest["schema"] = 99
        manifest["artifacts"]["wq"]["file"] = "nope.npz"

    _edit_manifest(tmp_path, corrupt)
    rep = verify_store(str(tmp_path))
    rules = {f.rule for f in rep.findings}
    assert "manifest" in rules and "arrays" in rules


def test_verify_store_tolerates_pre_planner_manifests(tmp_path):
    """Regression: stores written before the planner / lifecycle PRs carry
    no ``plan`` / ``device`` / ``t_service_s`` / ``sharding`` keys.  Both
    ``restore_programmed`` and ``verify_store`` must accept them."""
    params = _params()
    prog = _saved_store(tmp_path, params)

    def strip(manifest):
        for info in manifest["artifacts"].values():
            for key in ("plan", "device", "t_service_s", "sharding"):
                info.pop(key, None)

    _edit_manifest(tmp_path, strip)
    rep = verify_store(str(tmp_path), expected=expected_artifact_names(params))
    assert rep.ok, rep.summary()
    back = restore_programmed(str(tmp_path))
    art = back.by_name["wq"]
    assert art.plan is None and art.device is None and art.t_service_s == 0.0
    np.testing.assert_array_equal(
        np.asarray(art.g_eff), np.asarray(prog.by_name["wq"].g_eff)
    )


def test_engine_refuses_store_failing_static_verification(tmp_path):
    """ServingEngine(restore_artifacts=) runs verify_store fail-fast: an
    internally corrupt store is refused at construction with an error
    naming the checker, before any restore work happens."""
    from benchmarks.noise_sweep import tiny_lm_config
    from repro.models import model as M
    from repro.models.layers import CrossbarMode
    from repro.serving.engine import ServingEngine

    _saved_store(tmp_path, _params(), planned=False)

    def corrupt(manifest):
        manifest["artifacts"]["wq"]["spec"] = {"bogus_field": 1}

    _edit_manifest(tmp_path, corrupt)
    cfg = tiny_lm_config()
    params, _ = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    with pytest.raises(ValueError, match="static verification"):
        ServingEngine(
            cfg, params, max_batch=1, max_seq=16,
            crossbar=CrossbarMode(enabled=True),
            restore_artifacts=str(tmp_path),
        )
