"""Operations and bytes that the mathematics needs, from shapes alone.

Convention: a multiply-add is 2 operations.  Causal attention is counted at
what a causal (and windowed) mask leaves, not in full: a query at position i
attends to min(i + 1, window) keys.  Recomputation (remat) is not counted.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks.arch import Arch


def mean_context(seq: int, window: Optional[int]) -> float:
    """Keys a query attends to, averaged over the positions 0..seq-1."""
    w = seq if window is None else min(window, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Forward and backward: 6 per matmul weight, plus attention's two
    matmuls (QK^T and PV: 4 * context * heads * head_dim forward, x3)."""
    attn = 12.0 * a.layers * mean_context(seq, a.window) * a.heads * a.head_dim
    return 6.0 * a.matmul_params() + attn


def flash_fwd_bwd(a: Arch, batch: int, seq: int, itemsize: int = 2) -> Dict[str, float]:
    """One layer's flash attention, forward plus backward, for ``batch``
    sequences: forward 2 matmuls, backward 5 (it recomputes the scores)."""
    ctx = mean_context(seq, a.window)
    one = 2.0 * batch * a.heads * seq * ctx * a.head_dim  # one matmul
    q = batch * a.heads * seq * a.head_dim * itemsize
    kv = batch * a.kv_heads * seq * a.head_dim * itemsize
    # fwd reads q,k,v writes o; bwd reads q,k,v,o,do writes dq,dk,dv
    return {"flops": 7.0 * one, "bytes": (q + 2 * kv + q) + (3 * q + 2 * kv + q + 2 * kv)}


def paged_decode(a: Arch, live_tokens: float, slots: int,
                 itemsize: int = 2) -> Dict[str, float]:
    """One layer's paged decode call: every live KV position is read once,
    one query row per slot."""
    kv = 2.0 * live_tokens * a.kv_heads * a.head_dim * itemsize
    qo = 2.0 * slots * a.heads * a.head_dim * itemsize
    return {"flops": 4.0 * live_tokens * a.heads * a.head_dim,
            "bytes": kv + qo}


def roofline_seconds(cost: Dict[str, float], peak: Dict[str, float]) -> Dict[str, float]:
    """The least time the chip could take, and which bound sets it."""
    tc = cost["flops"] / peak["bf16_flops"]
    tm = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(tc, tm), "bound": "compute" if tc >= tm else "memory"}
