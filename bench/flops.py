"""Operations and bytes, counted from shapes, and the chip's peaks.

The kernels' work is what the datapath's semantics need, whatever
implements it: a (M, K) x (K, N) crossbar product does 2*M*K*N operations
and moves 2 bytes per 16-bit input code, 4 bytes per output element, and
the weights as the chip holds them (2 bytes per 16-bit code on an ideal
chip).  An implementation that does more work (the bit-sliced emulation
does 16 MXU passes) or stores more (int32 codes) does not change the count.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Sequence, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

X_BYTES = 2  # a 16-bit input code
OUT_BYTES = 4  # an output element


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, not a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def projections(dims) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every programmed projection one token passes
    through, the layers' repeated ``n_layers`` times, then the head."""
    D, F = dims.d_model, dims.d_ff
    q, kv = dims.n_heads * dims.head_dim, dims.n_kv_heads * dims.head_dim
    layer = [("wq", D, q), ("wk", D, kv), ("wv", D, kv), ("wo", q, D),
             ("wi", D, 2 * F), ("ffn_wo", F, D)]
    return layer * dims.n_layers + [("head", D, dims.vocab)]


def programmed_weights(dims) -> int:
    return sum(k * n for _, k, n in projections(dims))


def kernel_cost(m: int, k: int, n: int, weight_bytes: float) -> Tuple[float, float]:
    """(operations, bytes) of one (m, k) x (k, n) crossbar product."""
    return 2.0 * m * k * n, X_BYTES * m * k + OUT_BYTES * m * n + weight_bytes * k * n


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def decode_model_flops(dims, contexts: Sequence[int]) -> float:
    """Model operations of one decode step whose active rows attend over
    ``contexts`` positions each: 2 per programmed weight per row, and
    4 * heads * head_dim per layer per position attended (scores and
    values)."""
    attn = 4.0 * dims.n_heads * dims.head_dim * dims.n_layers
    return 2.0 * programmed_weights(dims) * len(contexts) + attn * float(sum(contexts))
