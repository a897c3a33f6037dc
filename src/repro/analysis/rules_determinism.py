"""Determinism lint: seeded randomness and pinned scale arithmetic.

Serving is contractually deterministic in (seed, admission order), and the
programmed-crossbar steady state is contractually *bit-identical* across
restarts/retraces.  Three statically checkable hazards:

* **unseeded RNG** — a ``PRNGKey``/``default_rng`` whose seed is neither a
  literal nor derived from an identifier containing "seed" breaks replay;
  module-level ``np.random.*`` samplers use hidden global state; and a
  host clock read (``time.time``, ``perf_counter``, ``monotonic``, their
  ``_ns`` forms) anywhere in ``src/`` injects time (allowlisted for the
  sites that only *report* it).
* **unpinned scale products** — PR 5 pinned FMA-contraction ULP flips by
  wrapping every product of two quantization scales in
  ``jax.lax.optimization_barrier`` (XLA may otherwise fuse
  ``(x * a) * b`` into ``x * (a * b)`` differently across retraces).  Any
  ``*_scale * *_scale`` arithmetic in the device family outside a barrier
  is a finding.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from repro.analysis.engine import (
    ERROR,
    Finding,
    dotted_name,
    parent_map,
    terminal_names,
)

RULE_RNG = "determinism-rng"
RULE_BARRIER = "determinism-barrier"

# whole-file allowlist for host clock reads: these report time, they never
# feed it into computation
TIME_ALLOW: Dict[str, str] = {
    "src/repro/train/loop.py": "step-time telemetry in training metrics",
    "src/repro/launch/dryrun.py": "compile-walltime reporting",
    "src/repro/launch/serve.py": "programming and serving time printed by the CLI",
}

# the host clocks of the ``time`` module
CLOCKS = {
    "time", "time_ns", "perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns",
}

# np.random attributes that touch the hidden global generator
_GLOBAL_SAMPLERS = {
    "seed", "rand", "randn", "randint", "random", "random_sample", "normal",
    "uniform", "choice", "permutation", "shuffle", "poisson", "exponential",
    "standard_normal", "binomial",
}

# files the barrier rule applies to: the programmed steady-state path and
# its lifecycle compensation
BARRIER_SCOPE = ("src/repro/device/",)


def _seed_ok(args: List[ast.AST]) -> bool:
    """A seed argument is acceptable if any part of it is an int literal or
    an identifier mentioning seed/key/rng/tag/chip (derived randomness)."""
    for arg in args:
        for n in ast.walk(arg):
            if isinstance(n, ast.Constant) and isinstance(n.value, int):
                return True
            name = None
            if isinstance(n, ast.Name):
                name = n.id
            elif isinstance(n, ast.Attribute):
                name = n.attr
            if name is not None and any(
                s in name.lower() for s in ("seed", "key", "rng", "tag", "chip")
            ):
                return True
    return False


def _clock_finding(relpath: str, line: int, clock: str) -> Finding:
    return Finding(
        RULE_RNG, relpath, line,
        f"host clock `{clock}` in src/ — outputs must be a function of "
        "(config, seed); allowlist reporting-only sites in "
        "rules_determinism.TIME_ALLOW",
    )


def rule_rng(relpath: str, tree: ast.Module, source: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            dn = dotted_name(node.func) or ""
            leaf = dn.split(".")[-1]
            if leaf == "PRNGKey":
                if not node.args or not _seed_ok(list(node.args)):
                    findings.append(Finding(
                        RULE_RNG, relpath, node.lineno,
                        f"PRNGKey seed `{ast.unparse(node)}` is neither a "
                        "literal nor derived from a seed — replay breaks",
                    ))
            elif leaf == "default_rng" and not node.args and not node.keywords:
                findings.append(Finding(
                    RULE_RNG, relpath, node.lineno,
                    "unseeded np.random.default_rng() — pass an explicit seed",
                ))
        elif isinstance(node, ast.Attribute):
            dn = dotted_name(node)
            if dn is None:
                continue
            parts = dn.split(".")
            if (
                len(parts) >= 3
                and parts[-3] in ("np", "numpy")
                and parts[-2] == "random"
                and parts[-1] in _GLOBAL_SAMPLERS
            ):
                findings.append(Finding(
                    RULE_RNG, relpath, node.lineno,
                    f"`{dn}` uses numpy's hidden global RNG state — use a "
                    "seeded np.random.default_rng(seed) generator",
                ))
            elif (
                len(parts) >= 2 and parts[-2] == "time" and parts[-1] in CLOCKS
                and relpath.startswith("src/") and relpath not in TIME_ALLOW
            ):
                findings.append(_clock_finding(relpath, node.lineno, dn))
        elif (
            isinstance(node, ast.ImportFrom) and node.module == "time"
            and relpath.startswith("src/") and relpath not in TIME_ALLOW
        ):
            for alias in node.names:
                if alias.name in CLOCKS:
                    findings.append(_clock_finding(relpath, node.lineno, f"time.{alias.name}"))
    # dedupe attribute findings that also appear inside a flagged Call, and
    # repeated Name/Attribute walks of the same node chain
    uniq = {}
    for f in findings:
        uniq[(f.rule, f.line, f.message)] = f
    return list(uniq.values())


def rule_barrier(relpath: str, tree: ast.Module, source: str) -> List[Finding]:
    if not relpath.startswith(BARRIER_SCOPE):
        return []
    parents = parent_map(tree)
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult)):
            continue
        left = {n for n in terminal_names(node.left)
                if n == "scale" or n.endswith("_scale")}
        right = {n for n in terminal_names(node.right)
                 if n == "scale" or n.endswith("_scale")}
        # the hazard is a product of two *different* scale values (the FMA
        # contraction XLA may reassociate across retraces); x/scale*scale
        # grid snaps and single-scale dequantizes are not it
        if not left or not right or left == right:
            continue
        # climb through arithmetic to the expression's owning call: the
        # product is pinned if any ancestor on the pure-expression chain is
        # an optimization_barrier call
        cur: ast.AST = node
        pinned = False
        while True:
            parent = parents.get(cur)
            if parent is None:
                break
            if isinstance(parent, ast.Call):
                dn = dotted_name(parent.func) or ""
                if dn.split(".")[-1] == "optimization_barrier":
                    pinned = True
                break
            if isinstance(parent, (ast.BinOp, ast.Tuple, ast.UnaryOp)):
                cur = parent
                continue
            break
        if not pinned:
            findings.append(Finding(
                RULE_BARRIER, relpath, node.lineno,
                f"scale product `{ast.unparse(node)}` is not pinned with "
                "jax.lax.optimization_barrier — XLA fusion may reassociate "
                "the FMA contraction and flip ULPs across retraces",
            ))
    return findings
