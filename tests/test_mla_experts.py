"""DeepSeek-V2 at one chip's share: latent attention with YaRN rope, the
latent norm and the per-head absorbed up-projections on the crossbar, and
an expert layer that holds some of the experts, routes over all of them,
drops nothing and counts what its dispatch does; and crossbar inputs
quantized row by row, which keep a request's outputs from its batch."""
import dataclasses as dc
import glob
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.rules_matmul import AUDIT
from repro.configs import get_config
from repro.configs.base import reduced
from repro.device import program_model
from repro.device.programmed import program_layer, programmed_linear
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import moe as Mo
from repro.serving import ContinuousBatchingScheduler, ModelRunner, tracing

LITE = get_config("deepseek-v2-lite")


def lite(**kw):
    """deepseek-v2-lite at CPU widths (one dense, two MoE layers), its
    mechanisms kept: YaRN, the latent norm, unnormalized gates."""
    return dc.replace(reduced(LITE), param_dtype="float32", **kw)


def dropless(cfg):
    """A capacity factor of experts over top-k: every held expert's buffer
    has a row for each token."""
    return dc.replace(cfg, moe_capacity_factor=cfg.moe_experts / cfg.moe_top_k)


def _params(cfg, seed=0):
    return M.init_model(jax.random.PRNGKey(seed), cfg, dtype=jnp.float32)[0]


def _gains(params, seed=1):
    """Random norm gains, so that a gain applied wrongly shows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    return jax.tree_util.tree_map_with_path(
        lambda p, a: 0.3 * jax.random.normal(next(keys), a.shape)
        if "norm" in str(p[-1].key) else a, params)


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_softmax_scale_follow_the_closed_form():
    inv_freq, scale = A.mla_rope(LITE)
    dim, base = 64, 10000.0
    # yarn_find_correction_range(32, 1, 64, 10000, 4096) = (10, 23)
    corr = [dim * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(base)) for r in (32, 1)]
    low, high = math.floor(corr[0]), math.ceil(corr[1])
    assert (low, high) == (10, 23)
    extra = base ** -(np.arange(0, dim, 2) / dim)
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = extra / 40 * (1 - keep) + extra * keep
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[:low + 1], extra[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[high:], extra[high:] / 40, rtol=1e-6)
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert scale == pytest.approx(0.1147, abs=1e-4)
    assert A.mla_rope(dc.replace(LITE, yarn_factor=0.0)) == (None, 192 ** -0.5)


def _mla_inputs(cfg, S=12):
    p = jax.tree.map(lambda a: a[0], _gains(_params(cfg))["stage0"]["b0"]["mixer"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, cfg.d_model))
    return p, x, jnp.arange(S)


def test_latent_is_normed_before_caching():
    cfg = lite()
    p, x, pos = _mla_inputs(cfg)
    cache = A.init_attention_cache(cfg, 2, 16, jnp.float32)
    _, new = A.attention_block(p, x, cfg, "attn", pos, cache)
    latent = (x @ p["w_kv_down"])[..., :cfg.kv_lora_rank]
    want = L.rms_norm(latent, p["kv_norm"], cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(new["latent"][:, :12]), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert not np.allclose(np.asarray(new["latent"][:, :12]), np.asarray(latent), atol=1e-2)


def _plain_mla(p, x, cfg, pos):
    """MLA with per-head keys and values materialised from the latent."""
    B, S, _ = x.shape
    H, dh, r, lora = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    inv_freq, scale = A.mla_rope(cfg)
    q = (x @ p["wq"]).reshape(B, S, H, dh + r)
    q_nope, q_rope = q[..., :dh], L.apply_rope(q[..., dh:], pos, cfg.rope_theta, inv_freq)
    kv = x @ p["w_kv_down"]
    c = L.rms_norm(kv[..., :lora], p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(kv[..., None, lora:], pos, cfg.rope_theta, inv_freq)[..., 0, :]
    k_nope = jnp.einsum("bsl,hdl->bshd", c, p["w_uk"])
    v = jnp.einsum("bsl,hld->bshd", c, p["w_uv"])
    s = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope)
         + jnp.einsum("bqhr,bsr->bhqs", q_rope, k_rope)) * scale
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(B, S, H * dh) @ p["wo"]


def test_absorbed_mla_equals_the_materialised_form():
    cfg = lite()
    p, x, pos = _mla_inputs(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = A.attention_block(p, x, cfg, "attn", pos)
        want = _plain_mla(p, x, cfg, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_no_known_audit_entry_in_attention():
    table = AUDIT["src/repro/models/attention.py"]
    assert table and all(status == "allow" for status, _ in table.values())


def test_per_head_calls_serve_each_heads_own_programmed_slab():
    """W_uk through the head-stacked artifact gives, head by head, the bits
    of that head's slab programmed and served alone."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, 16, 32)) / 4, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 2, 3, 16)), jnp.float32)
    prog = program_model({"w_uk": w})
    assert prog.by_name["w_uk"].w_codes.shape == (4, 16, 32)
    with L.crossbar_mode(L.CrossbarMode(enabled=True, programmed=prog, strict=True)):
        got = jax.jit(lambda x, w: L.grouped_linear(x, w, "w_uk"))(x, w)
    for h in range(4):
        want = jax.jit(programmed_linear)(x[h], program_layer(w[h]))
        np.testing.assert_array_equal(np.asarray(got[h]), np.asarray(want))


def test_strict_mode_zero_misses_with_w_uk_and_w_uv_programmed():
    cfg = lite()
    params = _params(cfg)
    prog = program_model(params)
    names = set(prog.by_name)
    for stage in ("stage0", "stage1"):
        assert {f"{stage}/b0/mixer/w_uk", f"{stage}/b0/mixer/w_uv"} <= names
    assert prog.by_name["stage1/b0/mixer/w_uk"].w_codes.shape == (2, 4, 16, 32)
    L.reset_crossbar_misses()
    import repro.device.programmed as P

    P.reset_consumed_artifact_names()
    cache = M.init_cache(cfg, 2, 8, jnp.float32)
    with L.crossbar_mode(L.CrossbarMode(enabled=True, programmed=prog, strict=True)):
        with prog.bind():
            jax.make_jaxpr(lambda p, t, c: M.decode_step(p, cfg, t, jnp.zeros(2, jnp.int32), c))(
                params, jnp.zeros((2, 1), jnp.int32), cache)
    assert L.crossbar_misses() == ()
    prog.verify_consumed()


# ---------------------------------------------------------------------------
# Experts held here
# ---------------------------------------------------------------------------


def _moe_inputs(cfg, seed=0):
    ini = L.Init(key=jax.random.PRNGKey(seed))
    Mo.init_moe(ini, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 12, cfg.d_model))
    return ini.params, x


def test_gates_are_the_raw_probabilities_unless_normalized():
    cfg = lite(moe_experts=16, moe_top_k=3)
    p, x = _moe_inputs(cfg)
    idx, gates, probs = Mo._route(x, p["router"], cfg)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(
        jnp.take_along_axis(probs, idx, axis=-1)), rtol=1e-6)
    _, normed, _ = Mo._route(x, p["router"], dc.replace(cfg, moe_norm_topk=True))
    np.testing.assert_allclose(np.asarray(normed.sum(-1)), 1.0, rtol=1e-5)


def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight EP ranks of 2 of 16 experts each: each rank's layer gives its
    experts' part (assignments elsewhere add nothing), and the parts with
    the shared experts counted once are the whole layer."""
    E, held = 16, 2
    whole = dropless(lite(moe_experts=E, moe_top_k=3))
    p, x = _moe_inputs(whole)
    with jax.default_matmul_precision("highest"):
        want = Mo.moe_ffn(p, x, whole)
        share_cfg = dc.replace(whole, moe_experts_held=held, moe_shared_experts=0)
        total = 0.0
        for r in range(E // held):
            # rank r's experts are the first it holds: rotate the router so
            # that its columns come first
            mine = {"router": jnp.roll(p["router"], -r * held, axis=1),
                    **{k: p[k][r * held:(r + 1) * held] for k in ("wi", "wg", "wo")}}
            total = total + Mo.moe_ffn(mine, x, share_cfg)
        shared = Mo._act(x @ p["shared_wi"], x @ p["shared_wg"], "swiglu") @ p["shared_wo"]
    assert p["wi"].shape[0] == E and mine["wi"].shape[0] == held
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def _counts(cfg, p, x):
    with L.collect_counts() as counts:
        Mo.moe_ffn(p, x, cfg)
    return {k: int(jnp.sum(v)) for k, v in counts.items()}


def test_dropped_reads_zero_when_every_token_routes_to_one_held_expert():
    cfg = dropless(lite(moe_experts=16, moe_experts_held=4, moe_top_k=1))
    p, x = _moe_inputs(cfg)
    p = dict(p, router=jnp.zeros_like(p["router"]))  # ties go to expert 0
    n = x.shape[0] * x.shape[1]
    assert _counts(cfg, p, x) == {"moe_held": n, "moe_rows": 4 * n, "moe_dropped": 0}
    # a capacity sized for even routing drops what expert 0 cannot take
    capped = _counts(dc.replace(cfg, moe_capacity_factor=1.25), p, x)
    assert capped["moe_held"] == n and capped["moe_dropped"] == n - 8


def test_fetch_span_carries_the_dispatch_counts(tmp_path):
    """The decode step's counts, and those of the admissions before it, are
    read with the logits and put on ``runner.fetch``; the assignments held
    here are counted over the rows that carry a request alone (the
    prompts, not their buckets' padding; the active slots, not the idle
    ones)."""
    cfg = dropless(lite(moe_experts_held=4))
    params = _params(cfg)
    # a router of zeros ties every expert: top-k takes experts 0..k-1, all
    # held, so each row carries exactly k held assignments a layer
    ffn = params["stage1"]["b0"]["ffn"]
    params["stage1"]["b0"]["ffn"] = dict(ffn, router=jnp.zeros_like(ffn["router"]))
    runner = ModelRunner(cfg, params, max_seq=64)
    sched = ContinuousBatchingScheduler(runner, max_batch=2)

    def serve():
        sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        sched.submit(np.arange(7, 16, dtype=np.int32), max_new_tokens=2)
        sched.run()

    serve()  # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    fetches = [dict(ev.stats) for plane in jax.profiler.ProfileData.from_file(path).planes
               if plane.name.startswith("/host:") for line in plane.lines
               for ev in line.events if ev.name == tracing.PREFIX + "runner.fetch"]
    assert len(fetches) == 3
    n_moe, k = 2, cfg.moe_top_k
    # two slots, each held expert's buffer of capacity 8; both slots active
    # in the first two steps, one in the last
    for f, active in zip(fetches, (2, 2, 1)):
        assert f["moe_rows"] == n_moe * 4 * 8 and f["moe_dropped"] == 0
        assert f["moe_held"] == n_moe * active * k
    # both prompts (5 and 9 tokens) fall in one 32-token bucket, admitted
    # before the first step
    first = fetches[0]
    assert first["admit_moe_rows"] == 2 * n_moe * 4 * 32 and first["admit_moe_dropped"] == 0
    assert first["admit_moe_held"] == n_moe * (5 + 9) * k
    assert all("admit_moe_rows" not in f for f in fetches[1:])


def _decode_slot0(cfg, params, prompts, steps=3):
    """Slot 0's decode logits with ``prompts`` admitted into slots 0, 1..."""
    from repro.launch.serve import make_runner
    from repro.serving.engine import Request

    runner = make_runner(cfg, params, max_seq=64)
    cache = runner.init_cache(2)
    toks, pos = np.zeros(2, np.int32), np.zeros(2, np.int32)
    for slot, prompt in enumerate(prompts):
        cache, pos[slot], toks[slot], _ = runner.admit_slot(cache, slot, Request(slot, prompt))
    out = []
    for _ in range(steps):
        logits, cache = runner.decode(toks, pos, cache)
        out.append(logits[0])
        toks, pos = np.argmax(logits, -1).astype(np.int32), pos + 1
    return np.stack(out)


@pytest.mark.parametrize("row_ranges", [True, False])
def test_row_ranges_keep_a_request_from_its_batch_neighbours(row_ranges):
    """With each input row quantized over its own range, a request's logits
    are the same bits whether it decodes alone or beside another request;
    over the call's range the neighbour moves them."""
    cfg = dropless(lite(moe_experts_held=4, crossbar_row_ranges=row_ranges))
    params = _gains(_params(cfg))
    a = np.arange(3, 14, dtype=np.int32)
    b = (np.arange(40, 69, dtype=np.int32) * 7) % cfg.vocab_size
    alone = _decode_slot0(cfg, params, [a])
    beside = _decode_slot0(cfg, params, [a, b])
    assert np.array_equal(alone, beside) == row_ranges
