"""Operations and bytes the algorithms need, from shapes alone.

The benchmark's own arithmetic: a later PR may change the program, not
this file.  ``model_flops_per_token`` is a copy of
``ray_lightning_tpu.telemetry.step_stats.model_flops_per_token``
(``benchmarks/tests`` checks they agree today).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; a kind not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to benchmarks/peaks.json with its source"
        )
    return table[device_kind]


def model_flops_per_token(cfg: Any, attn: str = "full") -> float:
    """Forward+backward matmul FLOPs per trained token of a GPT-2
    (backward = 2x forward, no credit for recomputation).  ``full``
    charges the whole S x S attention matrix (the published-MFU
    convention); ``causal`` the half the kernels execute."""
    d, L, s, V = cfg.d_model, cfg.n_layer, cfg.seq_len, cfg.vocab_size
    mm = 24 * L * d * d          # qkv + proj + mlp weight matmuls
    attn_term = 4 * L * s * d    # QK^T and AV
    if attn == "causal":
        attn_term /= 2
    head = 2 * d * V             # tied output head
    return 3.0 * (mm + attn_term + head)


def attention_kernel_cost(batch: int, n_head: int, seq: int, head_dim: int,
                          itemsize: int = 2) -> Dict[str, Dict[str, float]]:
    """What ONE call of causal attention over (batch, n_head, seq,
    head_dim) needs, forward and backward.

    FLOPs count the causal half of each S x S matmul, 2 per
    multiply-add: forward QK^T and PV (2 matmuls); backward dV, dP, dQ
    and dK (4 matmuls).  A flash backward also recomputes QK^T; that is
    the kernel's choice, not the algorithm's need, and is not counted —
    so the share reported against this is the lower (safer) one.
    Bytes are the least HBM traffic: each of q, k, v, o (and in the
    backward do, dq, dk, dv) read or written once.
    """
    bh = batch * n_head
    one_matmul = 2.0 * bh * seq * seq * head_dim / 2.0
    tensor = float(bh * seq * head_dim * itemsize)
    return {
        "forward": {"flops": 2 * one_matmul, "bytes": 4 * tensor},
        "backward": {"flops": 4 * one_matmul, "bytes": 8 * tensor},
    }


def roofline_seconds(cost: Dict[str, float], peaks: Dict[str, float]
                     ) -> Dict[str, Any]:
    """Least time the chip could take for ``cost``, and which bound
    sets it."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}
