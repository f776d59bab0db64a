"""Operations and bytes a kernel's call needs, from its shapes, and the
least time a chip with the given peaks could take for it. Part of the
yardstick. A kernel's share of its roofline is that least time over the
time the trace measured; the larger of the two terms names the bounding
side."""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple


def least_seconds(flops: float, bytes_moved: float, peaks: dict
                  ) -> Tuple[float, str]:
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


# -- flash attention (causal), per call over (B, H, T, D) -------------------
# One T x T x D matmul is 2*T*T*D operations; causal masking needs half.
# forward: S = QK^T, O = PV -> 2 matmuls. backward needs 5 (S again, dV,
# dP, dQ, dK); this program splits it in a dQ kernel (S, dP, dQ: 3) and a
# dK/dV kernel (S, dP, dV, dK: 4), and each call is held to what it computes.
FLASH_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}
# arrays of B*H*T*D elements read or written, bf16
FLASH_ARRAYS = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 7}


def flash_call(kind: str, B: int, H: int, T: int, D: int,
               itemsize: int = 2) -> Tuple[float, float]:
    flops = FLASH_MATMULS[kind] * 2.0 * B * H * T * T * D / 2.0
    bytes_moved = FLASH_ARRAYS[kind] * B * H * T * D * itemsize \
        + 2 * B * H * T * 4            # lse (and delta): float32 rows
    return flops, bytes_moved


def classify_flash(shape: str) -> Optional[str]:
    """Which flash kernel a custom call is, from its output shape: the
    forward returns (out, lse: float32 of lower rank); dK/dV returns two
    arrays of one shape; dQ returns one array. (``pallas_call`` passes no
    ``name=`` today, so the instruction's name says nothing.)"""
    arrays = re.findall(r"(\w+)\[([\d,]*)\]", shape)
    if len(arrays) == 1:
        return "flash_bwd_dq"
    if len(arrays) == 2:
        (t0, s0), (t1, s1) = arrays
        if t0 == t1 and s0 == s1:
            return "flash_bwd_dkv"
        return "flash_fwd"
    return None


def flash_roofline(custom_calls: Dict[str, dict], dims: dict, peaks: dict
                   ) -> Optional[dict]:
    """Least time over measured time for every custom call of a train step,
    if each one is a flash kernel; None if any is something else."""
    least = measured = 0.0
    sides = {}
    for call in custom_calls.values():
        kind = classify_flash(call["shape"])
        if kind is None:
            return None
        flops, bytes_moved = flash_call(kind, dims["B"], dims["H"],
                                        dims["T"], dims["D"])
        t, side = least_seconds(flops, bytes_moved, peaks)
        least += t * call["count"]
        measured += call["total_s"]
        sides[kind] = side
    if measured <= 0:
        return None
    return {"share": least / measured, "bound_by": sides,
            "least_s": least, "measured_s": measured}


# -- paged decode attention, per call (one layer, one step) -----------------
def paged_decode_call(cached_tokens: int, live_slots: int, kv_heads: int,
                      n_head: int, D: int, itemsize: int = 2
                      ) -> Tuple[float, float]:
    """Reads K and V of every cached token once, q and out per live slot."""
    flops = 2 * 2.0 * cached_tokens * n_head * D
    bytes_moved = 2.0 * cached_tokens * kv_heads * D * itemsize \
        + 2.0 * live_slots * n_head * D * itemsize
    return flops, bytes_moved
