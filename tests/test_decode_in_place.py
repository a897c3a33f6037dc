"""The decode step writes each token into the stage's cache in place.

``model.decode_step`` carries each stage's cache through its layer scan as
one buffer (``attention.LayerCache``), and ``ModelRunner.decode_fn``
donates it.  These tests hold the step to the semantics it replaced: each
layer's cache sliced out of the stage, written with ``_cache_write`` and
restacked, bit for bit, for every architecture, scanned and unrolled; and
a runner fed back its donated cache serves the tokens of one that is not.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import reduced
from repro.models import attention as A
from repro.models import model as M

B, S, STEPS = 3, 24, 4


class _SlicedLayer:
    """One layer's cache as a value of its own, held as the cache was laid
    out before the step wrote it in place (MLA's rope keys (B, S, rope),
    handed to the block with its unit heads axis): the token is written into
    the layer's arrays, which the caller restacks."""

    def __init__(self, entry, pos):
        self.entry, self.pos = dict(entry), pos

    def __getitem__(self, name):
        a = self.entry[name]
        return a[:, :, None] if name == "k_rope" else a

    def write(self, name, new):
        if name == "k_rope":
            new = new[:, :, 0]
        self.entry[name] = A._cache_write(self.entry[name], new, self.pos)
        return self[name]

    def overwrite(self, state):
        self.entry.update(state)


def _flat_rope(cache):
    """The cache with MLA's rope keys (L, B, S, 1, rope) as (L, B, S, rope)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a[..., 0, :] if "k_rope" in jax.tree_util.keystr(path) else a, cache)


def _reference_step(params, cfg, tok, pos, cache):
    """Decode with each layer's cache sliced out of its stage (scanned as
    ``xs`` or indexed), written, and restacked (``ys`` or ``jnp.stack``)."""
    x = M._embed_input(params, cfg, tok)
    positions = pos[:, None]
    new_cache = []
    for si, spec in enumerate(cfg.stages):
        def body(h, xs):
            lp, entries = xs
            new = {}
            for i, kind in enumerate(spec.kinds):
                view = _SlicedLayer(entries[f"b{i}"], pos)
                h, _ = M._apply_block(
                    lp[f"b{i}"], h, cfg, kind, bool(spec.moe[i]) and cfg.moe_experts > 0,
                    positions, view, pos)
                new[f"b{i}"] = view.entry
            return h, new

        xs = (params[f"stage{si}"], cache[si])
        if cfg.scan_layers:
            x, nc = jax.lax.scan(body, x, xs)
        else:
            outs = []
            for r in range(spec.repeats):
                x, y = body(x, jax.tree.map(lambda a: a[r], xs))
                outs.append(y)
            nc = jax.tree.map(lambda *ys: jnp.stack(ys), *outs)
        new_cache.append(nc)
    return M._logits(params, cfg, x)[:, 0], new_cache


def _tokens(cfg, key):
    if cfg.frontend == "embed":
        return jax.random.normal(key, (B, 1, cfg.d_model), jnp.float32)
    return jax.random.randint(key, (B, 1), 0, cfg.vocab_size)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("arch", configs.ALL_ARCHS)
def test_decode_matches_per_layer_reference(arch, scan):
    cfg = dataclasses.replace(reduced(configs.get_config(arch)), scan_layers=scan)
    key = jax.random.PRNGKey(7)
    params, _ = M.init_model(key, cfg)
    # a cache holding something at every position, so every read counts
    zeros = M.init_cache(cfg, B, S, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(zeros)
    keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
    cache = jax.tree.unflatten(
        tree, [jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    step = jax.jit(lambda p, t, q, c: M.decode_step(p, cfg, t, q, c))
    ref = jax.jit(lambda p, t, q, c: _reference_step(p, cfg, t, q, c))
    got, want = cache, _flat_rope(cache)
    for t in range(STEPS):
        # distinct positions per slot, one slot held at the last position
        pos = jnp.array([t, 9 + 2 * t, S - 1], jnp.int32)
        tok = _tokens(cfg, jax.random.fold_in(key, 10 + t))
        lg, got = step(params, tok, pos, got)
        lw, want = ref(params, tok, pos, want)
        assert np.array_equal(np.asarray(lg), np.asarray(lw)), f"logits differ at step {t}"
    for a, b in zip(jax.tree.leaves(_flat_rope(got)), jax.tree.leaves(want)):
        assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))


def test_cache_write_puts_each_slot_at_its_position():
    cache = jnp.zeros((2, 3, 6, 2), jnp.float32)  # (L, B, S, F)
    new = jnp.arange(1, 7, dtype=jnp.float32).reshape(3, 1, 2)
    pos = jnp.array([0, 4, 5], jnp.int32)
    out = np.asarray(A._cache_write(cache, new, pos, layer=1))
    want = np.zeros((2, 3, 6, 2), np.float32)
    for b in range(3):
        want[1, b, int(pos[b])] = np.asarray(new[b, 0])
    assert np.array_equal(out, want)
    # one layer, a position shared by every slot
    one = A._cache_write(jnp.zeros((3, 6, 2)), new, jnp.int32(2))
    assert np.array_equal(np.asarray(one)[:, 2], np.asarray(new[:, 0]))
    assert float(jnp.abs(one).sum()) == float(jnp.abs(new).sum())


def test_runner_decode_with_donated_cache_matches_undonated():
    from repro.launch.serve import make_runner
    from repro.serving.engine import Request

    cfg = reduced(configs.get_config("smollm-360m"))
    params, _ = M.init_model(jax.random.PRNGKey(3), cfg)
    runner = make_runner(cfg, params, max_seq=48)
    undonated = jax.jit(runner._on_chip(M.decode_step))
    cache = runner.init_cache(2)
    toks, pos = np.zeros(2, np.int32), np.zeros(2, np.int32)
    for slot, n in enumerate((5, 11)):
        prompt = (np.arange(n, dtype=np.int32) * (3 + slot)) % cfg.vocab_size
        cache, pos[slot], toks[slot], _ = runner.admit_slot(cache, slot, Request(slot, prompt))
    plain = jax.tree.map(jnp.copy, cache)
    t_don, t_plain = toks.copy(), toks.copy()
    for _ in range(3):
        fed = cache
        logits, cache = runner.decode(t_don, pos, cache)
        # the step took the cache it was given: the caller holds only the new one
        assert all(a.is_deleted() for a in jax.tree.leaves(fed))
        want, plain, _ = undonated(runner.artifacts, runner.params,
                                   jnp.asarray(t_plain[:, None]), jnp.asarray(pos), plain)
        t_don = runner.sample(logits)
        t_plain = runner.sample(np.asarray(want, np.float32))
        assert np.array_equal(t_don, t_plain)
        pos = pos + 1
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
