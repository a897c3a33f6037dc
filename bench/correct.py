"""Whether what the timed path served is correct: served tokens against a
plain reference that shares nothing with the program but the weights the
benchmark made.

A sample of the requests that finished inside the window, drawn from the
seed and always holding the longest, is run through the reference once
each: one forward over the prompt and its served tokens.  At each served
position the reference's logits give the gap by which the served token's
logit lies below the reference's best.  The number compared is the median
gap over the sample.

The crossbar datapath rounds every projection's output to a 16-bit window,
so a difference of one unit in the last place anywhere upstream flips a
few output codes, and over 32 layers the flips spread: no reference, and
no second run of the program with its batch composed otherwise, matches
the served logits bit for bit.  Where the reference's two best logits lie
within that rounding of each other, the program may serve the second, so
the widest gap over hundreds of tokens is a tail of the rounding; with a
datapath one precision lower it saturates at the logits' range, and the
two widest gaps lie less than three times apart.  The median gap is the
reference's choice on most tokens for the program and a wide miss for the
lower precision: the limit lies between (see PERF.md).

A second number holds the program to the precision the configuration
states for its KV cache (``serving.kv_cache_dtype``): the count of the
served cache's arrays in another dtype, limit 0.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MIN_TOKENS = 400  # served tokens in the sample


def sample(finished: Sequence[Tuple[np.ndarray, List[int], int]],
           seed: int) -> List[Tuple[np.ndarray, List[int]]]:
    """The longest of the ``(prompt, served, slot)`` of the finished
    requests, then others in a seeded order until the sample holds
    ``MIN_TOKENS`` served tokens (or all of them)."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -len(finished[i][1]))
    rest = order[1:]
    np.random.default_rng([int(seed), 7]).shuffle(rest)
    out, n = [], 0
    for i in [order[0]] + rest:
        out.append((finished[i][0], finished[i][1]))
        n += len(finished[i][1])
        if n >= MIN_TOKENS:
            break
    return out


def _forward_fn(ref, dims, bits, attn_dtype=None):
    @jax.jit
    def fn(params, tokens, n_valid):
        with jax.default_matmul_precision("highest"):
            return ref.forward(params, tokens, dims, bits, n_valid, attn_dtype)[0]
    return fn


def served_gaps(ref, params, dims, requests, length: int,
                controls: Optional[Dict[str, Tuple]] = None) -> Dict[str, np.ndarray]:
    """Per served token: ``gap``, its reference logit's distance below the
    reference's best; and for each control ``name: (bits, attn_dtype)``,
    the same gap of the token that the reference at that precision ranks
    first on the same prompt and tokens."""
    forward = _forward_fn(ref, dims, ref.Bits())
    lower = {name: _forward_fn(ref, dims, bits, dt) for name, (bits, dt) in (controls or {}).items()}
    out: Dict[str, List[np.ndarray]] = {"gap": [], **{name: [] for name in lower}}
    for prompt, served in requests:
        seq = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
        toks = np.zeros((1, length), np.int32)
        toks[0, :len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(seq))  # the positions predicting served[i]
        logits = np.asarray(forward(params, jnp.asarray(toks), len(seq)))[rows]
        best = logits.max(-1)
        out["gap"].append(best - logits[np.arange(len(rows)), np.asarray(served)])
        for name, fn in lower.items():
            top = np.asarray(fn(params, jnp.asarray(toks), len(seq)))[rows].argmax(-1)
            out[name].append(best - logits[np.arange(len(rows)), top])
    return {name: np.concatenate(g) if g else np.zeros(0) for name, g in out.items()}


def median_gap(gaps: np.ndarray) -> float:
    """The gap number compared; a sample with no token reads as an
    infinite gap."""
    return float(np.median(gaps)) if len(gaps) else float("inf")


def off_dtype_leaves(cache, dtype: str) -> int:
    """Arrays of the served KV cache not in ``dtype``."""
    return sum(1 for a in jax.tree.leaves(cache) if a.dtype != jnp.dtype(dtype))


def check(ref, params, dims, requests, length: int, limits: Dict[str, float],
          off_dtype: int) -> Tuple[Dict, Dict[str, np.ndarray]]:
    """The numbers compared, each with its limit, and the gaps they came
    from."""
    g = served_gaps(ref, params, dims, requests, length)
    values = {"median_gap": median_gap(g["gap"]), "kv_cache_off_dtype": off_dtype}
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}, g


def is_correct(cmp: Dict) -> bool:
    return all(v["value"] <= v["limit"] for v in cmp.values())


def describe(cmp: Dict) -> List[str]:
    """One plain line per number compared: its name, value and limit."""
    return [f"{k} {v['value']} limit {v['limit']}" for k, v in cmp.items()]
