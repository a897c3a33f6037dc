"""Operations and bytes of a crossbar product, counted from its shape, and
the chip's peaks: what holds for any model.  Which products one step of a
model makes, and its model operations, each reference module counts
(``decode_kernels``, ``decode_model_flops``).

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
from typing import Dict, Tuple

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


def kernel_cost(m: int, k: int, n: int, weight_bytes: float) -> Tuple[float, float]:
    """(operations, bytes) of one (m, k) x (k, n) crossbar product."""
    return 2.0 * m * k * n, X_BYTES * m * k + OUT_BYTES * m * n + weight_bytes * k * n


def roofline_s(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
