"""Digital-fallback detector: every matmul in ``src/repro/models/`` is
classified, or it is a finding.

Newton's premise only holds if every weight-bearing contraction reaches the
crossbar path (``models.layers.crossbar_linear`` -> programmed artifacts).
This rule inventories every ``jnp.dot`` / ``jnp.matmul`` / ``jnp.einsum`` /
``@`` site under ``src/repro/models/`` and checks it against an explicit
audit table keyed by ``(relpath, ast.unparse(site))``:

* ``allow`` — legitimately digital forever: weightless attention dots,
  recurrent scan state math, crossbar-disabled fallback branches that the
  runtime already guards (``current_crossbar().enabled`` /
  ``note_crossbar_gap``), and the one sanctioned dense fallback inside
  ``crossbar_linear`` itself.
* ``known`` — a *known-digital projection*: a weight contraction that has
  not been lifted onto the programmed path yet (ROADMAP #5's ssm/xlstm
  recurrent projections).  Reported as an
  ``info`` finding so the gap stays visible in every lint run instead of
  being folklore, but does not fail ``--check``.

Any site absent from the table is an ``error``: new matmuls in models/
must be deliberately classified before CI passes.  Keys are unparsed
source, not line numbers, so the table survives code motion and goes
stale loudly (an orphaned entry is itself a finding).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Tuple

from repro.analysis.engine import ERROR, INFO, Finding, dotted_name

RULE = "digital-fallback"

MATMUL_FUNCS = {"dot", "matmul", "einsum", "tensordot", "dot_general"}

# (relpath prefix the rule applies to)
SCOPE = "src/repro/models/"

# status: "allow" (legitimately digital) | "known" (known-digital projection,
# reported as info).  Keyed by exact ast.unparse of the site.
AUDIT: Dict[str, Dict[str, Tuple[str, str]]] = {
    "src/repro/models/ssm.py": {
        "x @ params['in_proj']": (
            "known", "mamba in_proj runs digital (ROADMAP #5 ssm lift)"),
        "xc @ params['x_proj']": (
            "known", "mamba x_proj runs digital (ROADMAP #5 ssm lift)"),
        "dt @ params['dt_proj']": (
            "known", "mamba dt_proj runs digital (ROADMAP #5 ssm lift)"),
        "y @ params['out_proj']": (
            "known", "mamba out_proj runs digital (ROADMAP #5 ssm lift)"),
        "jnp.einsum('bkd,kd->bd', window, params['conv_w'])": (
            "allow", "depthwise causal conv taps (K=d_conv) — not a dense slab"),
        "jnp.einsum('bdn,bn->bd', h, C_ssm.astype(jnp.float32)[:, 0])": (
            "allow", "weightless selective-scan state readout"),
        "jnp.einsum('bsdn,bsn->bsd', h_all, C_ssm.astype(jnp.float32))": (
            "allow", "weightless selective-scan state readout"),
    },
    "src/repro/models/xlstm.py": {
        # mLSTM chunk math: weightless q/k/v + per-chunk state tensors
        "jnp.einsum('bchd,bhde->bche', qf, C0)": (
            "allow", "weightless mLSTM inter-chunk state readout"),
        "jnp.einsum('bchd,bhd->bch', qf, n0)": (
            "allow", "weightless mLSTM normalizer readout"),
        "jnp.einsum('bthd,bshd->btsh', qf, kf)": (
            "allow", "weightless intra-chunk attention-form scores"),
        "jnp.einsum('btsh,bshe->bthe', scores, vf)": (
            "allow", "weightless intra-chunk value mix"),
        "jnp.einsum('btsh,bshd->bthd', D, kf)": (
            "allow", "weightless decay-weighted key sum"),
        "jnp.einsum('bhd,bth->bthd', n0, decay_t)": (
            "allow", "weightless normalizer decay"),
        "jnp.einsum('bthd,bthd->bth', n_tot, qf)": (
            "allow", "weightless normalizer dot"),
        "jnp.einsum('bch,bchd,bche->bhde', w_s, kf, vf)": (
            "allow", "weightless chunk state update (k (x) v outer)"),
        "jnp.einsum('bch,bchd->bhd', w_s, kf)": (
            "allow", "weightless chunk normalizer update"),
        # mLSTM decode recurrent state math (O(1) step)
        "jnp.einsum('bhd,bhe->bhde', kf, vf)": (
            "allow", "weightless decode state outer product"),
        "jnp.einsum('bhde,bhd->bhe', C1, qf)": (
            "allow", "weightless decode state readout"),
        "jnp.einsum('bhd,bhd->bh', n1, qf)": (
            "allow", "weightless decode normalizer dot"),
        # sLSTM recurrence: per-step hidden-to-hidden inside the scan body
        # (the slstm_scan Pallas kernel's domain — sequential step math, not
        # a programmable weight slab)
        "jnp.einsum('bhd,hde->bhe', h_, rz.astype(jnp.float32))": (
            "allow", "sequential sLSTM recurrence inside the scan step"),
        "jnp.einsum('bhd,hde->bhe', h_, ri.astype(jnp.float32))": (
            "allow", "sequential sLSTM recurrence inside the scan step"),
        "jnp.einsum('bhd,hde->bhe', h_, rf.astype(jnp.float32))": (
            "allow", "sequential sLSTM recurrence inside the scan step"),
        "jnp.einsum('bhd,hde->bhe', h_, ro.astype(jnp.float32))": (
            "allow", "sequential sLSTM recurrence inside the scan step"),
        # input/output projections: dense slabs still off the crossbar path
        "x @ params['wqkv']": (
            "known", "xLSTM qkv projection runs digital (ROADMAP #5 lift)"),
        "x @ params['w_gates']": (
            "known", "xLSTM gate projection runs digital (ROADMAP #5 lift)"),
        "x @ params['w_ogate']": (
            "known", "xLSTM output-gate projection runs digital (ROADMAP #5 lift)"),
        "y @ params['out_proj']": (
            "known", "mLSTM out_proj runs digital (ROADMAP #5 lift)"),
        "x @ params['w_in']": (
            "known", "sLSTM input projection runs digital (ROADMAP #5 lift)"),
        "y.astype(x.dtype) @ params['out_proj']": (
            "known", "sLSTM out_proj runs digital (ROADMAP #5 lift)"),
    },
    "src/repro/models/attention.py": {
        "jnp.einsum('bqgrd,bsgd->bqgrs', q, k, preferred_element_type=jnp.float32)": (
            "allow", "weightless GQA attention scores"),
        "jnp.einsum('bqgrs,bsgd->bqgrd', p, v.astype(p.dtype))": (
            "allow", "weightless GQA value mix"),
        "jnp.einsum('bqhl,bsl->bqhs', q_abs_blk.astype(latent_k.dtype), latent_k, preferred_element_type=jnp.float32, precision=HIGHEST)": (
            "allow", "weightless MLA scores vs cached latents"),
        "jnp.einsum('bqhr,bsr->bqhs', q_rope_blk.astype(rope_k.dtype), rope_k, preferred_element_type=jnp.float32, precision=HIGHEST)": (
            "allow", "weightless MLA rope scores vs cached keys"),
        "jnp.einsum('bqhs,bsl->bqhl', p.astype(latent_k.dtype), latent_k, preferred_element_type=jnp.float32, precision=HIGHEST)": (
            "allow", "weightless MLA latent value mix"),
    },
    "src/repro/models/moe.py": {
        # digital fallback branch: runs only when current_crossbar().enabled
        # is False (the crossbar-off serving mode)
        "jnp.einsum('ecd,edf->ecf', h, wi)": (
            "allow", "crossbar-disabled digital branch (guarded by "
                     "current_crossbar().enabled)"),
        "jnp.einsum('ecd,edf->ecf', h, wg)": (
            "allow", "crossbar-disabled digital branch (guarded by "
                     "current_crossbar().enabled)"),
        "jnp.einsum('ecf,efd->ecd', a, wo)": (
            "allow", "crossbar-disabled digital branch (guarded by "
                     "current_crossbar().enabled)"),
        # runtime-audited gaps: note_crossbar_gap records these misses
        "jnp.einsum('ecd,edf->ecf', h, w_l)": (
            "known", "grouped expert fallback runs digital — runtime-audited "
                     "via note_crossbar_gap"),
        "xf @ rw_l.astype(xf.dtype)": (
            "known", "router projection runs digital — runtime-audited via "
                     "note_crossbar_gap('router')"),
    },
    "src/repro/models/layers.py": {
        "x @ w": (
            "allow", "the sanctioned dense fallback inside crossbar_linear "
                     "itself — guarded by the miss counter and strict="),
        "jnp.einsum('g...k,gkn->g...n', x, w)": (
            "allow", "crossbar-disabled digital branch of grouped_linear "
                     "(guarded by _CROSSBAR.enabled)"),
    },
    "src/repro/models/model.py": {},
}


def _matmul_sites(tree: ast.Module) -> List[ast.AST]:
    sites = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
            sites.append(node)
        elif isinstance(node, ast.Call):
            dn = dotted_name(node.func)
            if dn is not None and dn.split(".")[-1] in MATMUL_FUNCS:
                sites.append(node)
    return sites


def rule_digital_fallback(relpath: str, tree: ast.Module, source: str) -> List[Finding]:
    if not relpath.startswith(SCOPE):
        return []
    table = AUDIT.get(relpath, {})
    findings: List[Finding] = []
    seen = set()
    for node in _matmul_sites(tree):
        key = ast.unparse(node)
        seen.add(key)
        entry = table.get(key)
        if entry is None:
            findings.append(Finding(
                RULE, relpath, node.lineno,
                f"unclassified matmul site: `{key}` — route it through "
                "crossbar_linear or add an 'allow'/'known' entry to "
                "repro.analysis.rules_matmul.AUDIT",
            ))
        elif entry[0] == "known":
            findings.append(Finding(
                RULE, relpath, node.lineno,
                f"known-digital projection: `{key}` ({entry[1]})",
                level=INFO,
            ))
        elif entry[0] != "allow":
            findings.append(Finding(
                RULE, relpath, node.lineno,
                f"bad AUDIT status {entry[0]!r} for `{key}` "
                "(must be 'allow' or 'known')",
            ))
    # stale entries: audited sites that no longer exist go loudly, so the
    # table can never accrete dead reassurances
    for key in table:
        if key not in seen:
            findings.append(Finding(
                RULE, relpath, 0,
                f"stale AUDIT entry (site no longer in file): `{key}`",
            ))
    return findings
