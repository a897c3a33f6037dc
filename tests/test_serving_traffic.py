"""Serving traffic tier: request-lifecycle regression tests + the
continuous-batching scheduler / block KV cache / chip farm.

The first three test groups pin the ISSUE 10 engine bugfixes — each fails
on the pre-fix engine:

  * ``run_until_done`` used to lose a request that was admitted and
    finished within one ``step()`` (its slot was freed before the loop's
    ``seen`` snapshot ever saw it);
  * ``_admit`` used to silently truncate a prompt longer than ``max_seq``
    while pointing ``pos``/``last_tok`` past the prefilled region
    (incoherent state, garbage generation);
  * ``hot_swap`` used to skip the ``analysis.verify_store`` fail-fast
    verification that construction-time ``restore_artifacts=`` runs, so a
    corrupt store hit mid-flight serving instead of being refused.

The rest covers the tentpole: scheduler determinism and bit-identity to
the slot-loop engine, deadlines/streaming/preemption, block accounting,
exact page-out/page-in, and farm routing/drain/refresh.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.noise_sweep import tiny_lm_config
from repro.device import DeviceConfig
from repro.models import model as M
from repro.models.layers import CrossbarMode
from repro.serving import (
    BlockCacheConfig,
    BlockKVCache,
    ChipFarm,
    ContinuousBatchingScheduler,
    ModelRunner,
    ServingEngine,
)

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = tiny_lm_config()
    params, _ = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _prompt(n, lo=1):
    return (np.arange(lo, lo + n) % 60 + 1).astype(np.int32)


# ---------------------------------------------------------------------------
# Bugfix 1: a request admitted and finished inside one step() must not
# vanish from run_until_done()
# ---------------------------------------------------------------------------


def test_one_token_request_round_trip(tiny_lm):
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    rid = eng.submit(_prompt(5), max_new_tokens=1)
    res = eng.run_until_done()
    assert [r.rid for r in res] == [rid]
    assert res[0].done and len(res[0].generated) == 1


def test_one_token_request_not_lost_among_longer(tiny_lm):
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    r0 = eng.submit(_prompt(5), max_new_tokens=1)
    r1 = eng.submit(_prompt(7), max_new_tokens=6)
    r2 = eng.submit(_prompt(4), max_new_tokens=1)
    res = eng.run_until_done()
    assert [r.rid for r in res] == [r0, r1, r2]
    assert all(r.done for r in res)
    assert [len(r.generated) for r in res] == [1, 6, 1]


def test_step_records_completion_ledger(tiny_lm):
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=32)
    rid = eng.submit(_prompt(5), max_new_tokens=1)
    assert eng.step() == 1
    # the slot was freed the same step, but the request is in the ledger
    assert eng.slots == [None]
    assert rid in eng._completed and eng._completed[rid].done


# ---------------------------------------------------------------------------
# Bugfix 2: over-length prompts — loud rejection, coherent truncation
# ---------------------------------------------------------------------------


def test_overlength_prompt_rejected_at_submit(tiny_lm):
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=16)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        eng.submit(_prompt(24))
    # nothing was queued: the engine stays clean after the refusal
    assert eng.pending == [] and eng.run_until_done() == []


def test_overlength_prompt_truncates_coherently(tiny_lm):
    cfg, params = tiny_lm
    long = _prompt(24)
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=16)
    eng.submit(long, max_new_tokens=3, truncate=True)
    res = eng.run_until_done()
    # truncate=True must behave exactly like submitting prompt[:max_seq]:
    # pos and last_tok come from the truncated length, not the original
    ref = ServingEngine(cfg, params, max_batch=1, max_seq=16)
    ref.submit(long[:16], max_new_tokens=3)
    ref_res = ref.run_until_done()
    assert res[0].generated == ref_res[0].generated
    assert res[0].done


def test_max_length_prompt_still_admits(tiny_lm):
    # the boundary case: S == max_seq is legal without truncate
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, max_batch=1, max_seq=16)
    eng.submit(_prompt(16), max_new_tokens=2)
    res = eng.run_until_done()
    assert res[0].done and len(res[0].generated) >= 1


# ---------------------------------------------------------------------------
# Bugfix 3: hot_swap must verify the store before rebinding
# ---------------------------------------------------------------------------


@pytest.fixture()
def programmed_engine(tiny_lm):
    cfg, params = tiny_lm
    dev = DeviceConfig(sigma=0.02, seed=3)
    return ServingEngine(
        cfg, params, max_batch=1, max_seq=16,
        crossbar=CrossbarMode(enabled=True, device=dev),
    )


def test_hot_swap_refuses_corrupted_store(programmed_engine, tmp_path):
    eng = programmed_engine
    d = str(tmp_path / "store")
    eng.save_artifacts(d)
    before = eng.crossbar.programmed
    # tamper: append a bogus array member to one artifact's npz.
    # restore_programmed ignores unknown members (it loads by key), so the
    # pre-fix hot_swap bound this store silently; verify_store's manifest/
    # npz-header cross-check flags it
    store = os.path.join(d, "programmed")
    with open(os.path.join(store, "manifest.json")) as f:
        man = json.load(f)
    rec = next(iter(man["artifacts"].values()))
    fname = os.path.join(store, rec["file"])
    arrs = dict(np.load(fname, allow_pickle=False))
    arrs["bogus_extra"] = np.zeros(3, np.float32)
    np.savez(fname, **arrs)
    with pytest.raises(ValueError, match="verify_store"):
        eng.hot_swap(d)
    # the refusal is fail-fast: the old chip is still bound and serving
    assert eng.crossbar.programmed is before
    eng.submit(_prompt(4), max_new_tokens=1)
    assert len(eng.run_until_done()) == 1


@pytest.mark.slow
def test_hot_swap_clean_store_still_works(programmed_engine, tmp_path):
    eng = programmed_engine
    d = str(tmp_path / "store")
    eng.save_artifacts(d)
    eng.hot_swap(d)  # same chip round-tripped: swap must succeed
    eng.submit(_prompt(4), max_new_tokens=2)
    res = eng.run_until_done()
    assert res[0].done


def test_hot_swap_and_restore_share_verification(programmed_engine, tiny_lm, tmp_path):
    # the fix routes hot_swap through the same _verify_store helper that
    # construction-time restore uses: a store both accept is identical,
    # and a store construction refuses hot_swap must refuse too
    cfg, params = tiny_lm
    eng = programmed_engine
    d = str(tmp_path / "store")
    eng.save_artifacts(d)
    store = os.path.join(d, "programmed")
    man_path = os.path.join(store, "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    # drop one artifact from the manifest: a missing-leaf store
    man["artifacts"].pop(sorted(man["artifacts"])[0])
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.raises(ValueError):
        ServingEngine(
            cfg, params, max_batch=1, max_seq=16,
            crossbar=CrossbarMode(enabled=True, device=DeviceConfig(sigma=0.02, seed=3)),
            restore_artifacts=d,
        )
    with pytest.raises(ValueError):
        eng.hot_swap(d)


# ---------------------------------------------------------------------------
# Tentpole: scheduler determinism + bit-identity to the engine
# ---------------------------------------------------------------------------


def _mixed_workload():
    return [
        (_prompt(5), 3),
        (_prompt(9, lo=4), 6),
        (_prompt(3, lo=9), 1),
        (_prompt(12, lo=2), 4),
        (_prompt(6, lo=7), 5),
        (_prompt(4, lo=11), 2),
    ]


def test_scheduler_token_identical_to_engine(tiny_lm):
    cfg, params = tiny_lm
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, seed=0)
    for p, n in _mixed_workload():
        eng.submit(p, max_new_tokens=n)
    eng_out = {r.rid: r.generated for r in eng.run_until_done()}

    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=32, seed=0), max_batch=2
    )
    for p, n in _mixed_workload():
        sched.submit(p, max_new_tokens=n)
    sched_out = {r.rid: r.generated for r in sched.run()}
    assert sched_out == eng_out


def test_scheduler_deterministic_replay(tiny_lm):
    cfg, params = tiny_lm

    def run():
        sched = ContinuousBatchingScheduler(
            ModelRunner(cfg, params, max_seq=32, seed=0), max_batch=2
        )
        for p, n in _mixed_workload():
            sched.submit(p, max_new_tokens=n)
        return [(r.rid, tuple(r.generated), r.finish) for r in sched.run()]

    assert run() == run()


def test_scheduler_admits_mid_flight(tiny_lm):
    # continuous batching: a request submitted while others decode is
    # admitted at the next tick, not after the batch drains
    cfg, params = tiny_lm
    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=32, seed=0), max_batch=2
    )
    sched.submit(_prompt(5), max_new_tokens=8)
    sched.step()
    sched.submit(_prompt(4, lo=3), max_new_tokens=2)
    sched.step()
    assert sched.n_active == 2  # joined the in-flight batch immediately
    res = sched.run()
    assert [len(r.generated) for r in res] == [8, 2]


def test_scheduler_deadline_eviction(tiny_lm):
    cfg, params = tiny_lm
    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=48, seed=0), max_batch=1
    )
    r0 = sched.submit(_prompt(4), max_new_tokens=30, deadline=3)
    r1 = sched.submit(_prompt(4, lo=2), max_new_tokens=2)
    res = {r.rid: r for r in sched.run()}
    assert res[r0].expired and res[r0].done
    assert len(res[r0].generated) <= 3  # got at most its deadline's ticks
    # the evicted slot freed capacity: the second request completed fully
    assert not res[r1].expired and len(res[r1].generated) == 2


def test_scheduler_edf_admission_order(tiny_lm):
    # a tight-deadline latecomer must be admitted before an earlier
    # deadline-free request when one slot frees up
    cfg, params = tiny_lm
    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=48, seed=0), max_batch=1
    )
    sched.submit(_prompt(4), max_new_tokens=2)  # occupies the only slot
    r_late = sched.submit(_prompt(4, lo=5), max_new_tokens=2, deadline=8)
    r_free = sched.submit(_prompt(4, lo=3), max_new_tokens=2)
    res = {r.rid: r for r in sched.run()}
    assert not res[r_late].expired
    # EDF: the deadlined request finished before the deadline-free one
    assert res[r_late].finish < res[r_free].finish


def test_scheduler_streaming_callbacks(tiny_lm):
    cfg, params = tiny_lm
    seen = []
    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=32, seed=0),
        max_batch=2,
        stream=lambda req, tok: seen.append((req.rid, tok)),
    )
    r0 = sched.submit(_prompt(5), max_new_tokens=3)
    per_req = []
    r1 = sched.submit(
        _prompt(4, lo=2), max_new_tokens=2,
        on_token=lambda req, tok: per_req.append(tok),
    )
    res = {r.rid: r for r in sched.run()}
    # the scheduler-wide stream saw r0's tokens as they were sampled...
    assert [t for rid, t in seen if rid == r0] == res[r0].generated
    # ...and the per-request callback overrode it for r1
    assert per_req == res[r1].generated
    assert all(rid != r1 for rid, _ in seen)


def test_scheduler_preemption_is_exact(tiny_lm):
    # a pool too small for both requests forces swap-out/swap-in; the
    # token streams must be bit-identical to the unconstrained engine
    cfg, params = tiny_lm
    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=48, seed=0),
        max_batch=2,
        block=BlockCacheConfig(block_size=4, n_blocks=4),
    )
    sched.submit(_prompt(6), max_new_tokens=8)
    sched.submit(_prompt(8, lo=2), max_new_tokens=8)
    out = {r.rid: r.generated for r in sched.run()}

    eng = ServingEngine(cfg, params, max_batch=2, max_seq=48, seed=0)
    eng.submit(_prompt(6), max_new_tokens=8)
    eng.submit(_prompt(8, lo=2), max_new_tokens=8)
    ref = {r.rid: r.generated for r in eng.run_until_done()}
    assert out == ref


def test_scheduler_refuses_impossible_request(tiny_lm):
    # admission control: a request whose worst-case block footprint
    # exceeds the whole pool would thrash forever — refused at submit
    cfg, params = tiny_lm
    sched = ContinuousBatchingScheduler(
        ModelRunner(cfg, params, max_seq=48, seed=0),
        max_batch=2,
        block=BlockCacheConfig(block_size=4, n_blocks=4),
    )
    with pytest.raises(ValueError, match="never run to completion"):
        sched.submit(_prompt(20), max_new_tokens=20)


# ---------------------------------------------------------------------------
# Block KV cache: accounting + exact paging
# ---------------------------------------------------------------------------


def test_block_accounting(tiny_lm):
    cfg, _ = tiny_lm
    kv = BlockKVCache(cfg, max_batch=2, max_seq=32,
                      block=BlockCacheConfig(block_size=8, n_blocks=6))
    assert kv.blocks_for(1) == 1 and kv.blocks_for(8) == 1
    assert kv.blocks_for(9) == 2 and kv.blocks_for(32) == 4
    kv.allocate(0, 9)
    assert kv.table(0) == (0, 1) and kv.free_blocks == 4
    assert kv.ensure(0, 16)  # still 2 blocks
    assert kv.table(0) == (0, 1)
    assert kv.ensure(0, 17)  # crosses into a third block
    assert kv.table(0) == (0, 1, 2) and kv.free_blocks == 3
    kv.allocate(1, 24)
    assert kv.free_blocks == 0
    assert not kv.ensure(0, 25)  # pool dry
    kv.release(1)
    assert kv.free_blocks == 3 and kv.ensure(0, 25)
    kv.release(0)
    assert kv.free_blocks == 6


def test_block_pool_default_matches_dense_capacity(tiny_lm):
    cfg, _ = tiny_lm
    kv = BlockKVCache(cfg, max_batch=4, max_seq=48)
    # default sizing: the pool can hold max_batch full-length requests
    assert kv.n_blocks == 4 * kv.blocks_for(48)
    for rid in range(4):
        kv.allocate(rid, 48)
    assert kv.free_blocks == 0


def test_page_out_in_round_trip_exact(tiny_lm):
    cfg, params = tiny_lm
    runner = ModelRunner(cfg, params, max_seq=32, seed=0)
    kv = BlockKVCache(cfg, max_batch=2, max_seq=32,
                      block=BlockCacheConfig(block_size=4))
    from repro.serving.engine import Request

    req = Request(0, _prompt(6), max_new_tokens=4)
    kv.allocate(0, 6)
    kv.cache, pos, last, _ = runner.admit_slot(kv.cache, 0, req)
    want = jax.tree.map(lambda l: np.asarray(l[:, 0]), kv.cache)
    # page out, trash the slot, page back into a *different* slot index,
    # then move it home: the prefix must round-trip bit-exactly
    kv.page_out(0, 0, pos, last)
    kv.cache = jax.tree.map(lambda l: l.at[:, 0].set(-1.0), kv.cache)
    pos2, last2 = kv.page_in(0, 1)
    assert (pos2, last2) == (pos, last)
    got = jax.tree.map(lambda l: np.asarray(l[:, 1]), kv.cache)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        # positions < pos are the request's prefix: must match exactly
        np.testing.assert_array_equal(w[:, :pos], g[:, :pos])


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "jamba-v0.1-52b"])
def test_page_round_trip_follows_each_leafs_sequence_axis(arch):
    """MLA's latents and rope keys (with their unit heads axis) and
    recurrent states, which have no sequence: a paged slot's prefix comes
    back whole, and a state whole."""
    from repro import configs
    from repro.configs.base import reduced

    cfg = reduced(configs.get_config(arch))
    kv = BlockKVCache(cfg, max_batch=2, max_seq=24, block=BlockCacheConfig(block_size=4))
    leaves, tree = jax.tree.flatten(kv.cache)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves))
    kv.cache = jax.tree.unflatten(
        tree, [jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    want = jax.tree.map(lambda l: np.asarray(l[:, 0]), kv.cache)
    pos = 10
    kv.allocate(0, pos)
    kv.page_out(0, 0, pos, 3)
    kv.cache = jax.tree.map(lambda l: l.at[:, 1].set(-1.0), kv.cache)
    assert kv.page_in(0, 1) == (pos, 3)
    axes = M.cache_axes(cfg)
    flat_axes = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    for ax, w, leaf in zip(flat_axes, jax.tree.leaves(want), jax.tree.leaves(kv.cache)):
        g = np.asarray(leaf[:, 1])
        if "cache_seq" not in ax:
            np.testing.assert_array_equal(w, g)
            continue
        seq = ax.index("cache_seq") - 1  # the slot axis is dropped
        np.testing.assert_array_equal(np.take(w, range(pos), seq), np.take(g, range(pos), seq))
        assert (np.take(g, range(pos, g.shape[seq]), seq) == -1.0).all()


def test_page_out_frees_blocks(tiny_lm):
    cfg, params = tiny_lm
    runner = ModelRunner(cfg, params, max_seq=32, seed=0)
    kv = BlockKVCache(cfg, max_batch=1, max_seq=32,
                      block=BlockCacheConfig(block_size=4, n_blocks=4))
    from repro.serving.engine import Request

    kv.allocate(7, 6)
    kv.cache, pos, last, _ = runner.admit_slot(
        kv.cache, 0, Request(7, _prompt(6), max_new_tokens=2)
    )
    held = kv.free_blocks
    kv.page_out(7, 0, pos, last)
    assert kv.is_paged(7) and kv.paged_pos(7) == pos
    assert kv.free_blocks > held  # swap-out relieves pool pressure
    kv.page_in(7, 0)
    assert not kv.is_paged(7) and kv.free_blocks == held


# ---------------------------------------------------------------------------
# Chip farm: routing, scaling, drain/refresh
# ---------------------------------------------------------------------------


def test_farm_round_robin_routing(tiny_lm):
    cfg, params = tiny_lm
    farm = ChipFarm(cfg, params, n_replicas=3, policy="round_robin",
                    max_batch=1, max_seq=32)
    rids = [farm.submit(_prompt(4, lo=k), max_new_tokens=1) for k in range(6)]
    assert [farm.replica_of(r) for r in rids] == [0, 1, 2, 0, 1, 2]
    res = farm.run_until_done()
    assert sorted(r.rid for r in res) == sorted(rids)
    assert all(r.done for r in res)


def test_farm_least_loaded_routing(tiny_lm):
    cfg, params = tiny_lm
    farm = ChipFarm(cfg, params, n_replicas=2, policy="least_loaded",
                    max_batch=1, max_seq=32)
    a = farm.submit(_prompt(4), max_new_tokens=4)
    b = farm.submit(_prompt(4, lo=2), max_new_tokens=4)
    # both replicas loaded 1 each; the third goes to the lowest index
    c = farm.submit(_prompt(4, lo=3), max_new_tokens=1)
    assert {farm.replica_of(a), farm.replica_of(b)} == {0, 1}
    assert farm.replica_of(c) == 0
    assert len(farm.run_until_done()) == 3


def test_farm_rids_disjoint_and_results_merge(tiny_lm):
    cfg, params = tiny_lm
    farm = ChipFarm(cfg, params, n_replicas=2, max_batch=2, max_seq=32)
    rids = [farm.submit(_prompt(5, lo=k), max_new_tokens=2) for k in range(4)]
    assert len(set(rids)) == 4
    res = farm.run_until_done()
    assert [r.rid for r in res] == sorted(rids)


def test_farm_single_replica_matches_engine(tiny_lm):
    cfg, params = tiny_lm
    farm = ChipFarm(cfg, params, n_replicas=1, max_batch=2, max_seq=32, seed=0)
    for p, n in _mixed_workload():
        farm.submit(p, max_new_tokens=n)
    farm_out = [r.generated for r in farm.run_until_done()]
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, seed=0)
    for p, n in _mixed_workload():
        eng.submit(p, max_new_tokens=n)
    eng_out = [r.generated for r in eng.run_until_done()]
    assert farm_out == eng_out


def test_farm_drain_stops_admission_not_service(tiny_lm):
    cfg, params = tiny_lm
    farm = ChipFarm(cfg, params, n_replicas=2, max_batch=1, max_seq=32)
    r0 = farm.submit(_prompt(4), max_new_tokens=4)  # lands on replica 0
    farm.drain(0)
    # new traffic avoids the draining replica...
    rids = [farm.submit(_prompt(4, lo=k), max_new_tokens=1) for k in range(3)]
    assert all(farm.replica_of(r) == 1 for r in rids)
    # ...but its in-flight request still runs to completion
    res = {r.rid: r for r in farm.run_until_done()}
    assert res[r0].done and len(res[r0].generated) == 4
    with pytest.raises(ValueError, match="draining"):
        farm.drain(1)
        farm.submit(_prompt(4))
    farm.undrain(0)
    farm.submit(_prompt(4), max_new_tokens=1)
    assert len(farm.run_until_done()) == 5


@pytest.mark.slow
def test_farm_drain_refresh_undrain_cycle(tiny_lm, tmp_path):
    # the lifecycle story: an aged replica is drained, refreshed from a
    # store commit, and undrained — without dropping the other replica's
    # traffic, and serving bit-identically afterwards
    cfg, params = tiny_lm
    dev = DeviceConfig(sigma=0.02, drift_nu=0.05, seed=3)
    d = str(tmp_path / "store")
    seedling = ServingEngine(
        cfg, params, max_batch=1, max_seq=16,
        crossbar=CrossbarMode(enabled=True, device=dev),
    )
    seedling.save_artifacts(d)
    farm = ChipFarm(
        cfg, params, n_replicas=2, max_batch=1, max_seq=16,
        crossbar=CrossbarMode(enabled=True, device=dev), restore_artifacts=d,
    )
    farm.replicas[0].age(3600.0)
    assert farm.uptimes()[0] > 0.0 and farm.uptimes()[1] == 0.0
    farm.drain(0)
    keep = farm.submit(_prompt(4), max_new_tokens=2)  # routed to replica 1
    assert farm.replica_of(keep) == 1
    assert farm.is_idle(0)
    farm.refresh(0, d)  # reprogram into the inactive slot + hot swap
    farm.undrain(0)
    assert farm.uptimes()[0] == 0.0
    back = farm.submit(_prompt(4, lo=2), max_new_tokens=2)
    res = {r.rid: r for r in farm.run_until_done()}
    assert res[keep].done and res[back].done
    # the refreshed replica serves exactly what a fresh restore serves
    ref = ServingEngine(
        cfg, params, max_batch=1, max_seq=16,
        crossbar=CrossbarMode(enabled=True, device=dev), restore_artifacts=d,
    )
    ref.submit(_prompt(4, lo=2), max_new_tokens=2)
    assert ref.run_until_done()[0].generated == res[back].generated


def test_farm_rejects_bad_config(tiny_lm):
    cfg, params = tiny_lm
    with pytest.raises(ValueError, match="n_replicas"):
        ChipFarm(cfg, params, n_replicas=0)
    with pytest.raises(ValueError, match="policy"):
        ChipFarm(cfg, params, n_replicas=1, policy="random")
