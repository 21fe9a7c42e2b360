"""Operations and bytes of the dropless expert layer's grouped matmuls
(``ops/moe.py`` kernels ``rlt_moe_gate_up`` and ``rlt_moe_down``), from
shapes and the program's own counters.

Per routed assignment that fell on a held expert the two kernels do
three ``d x f`` matmuls (gate, up, down): ``6 d f`` FLOPs.  The least
HBM traffic is the weights of the held experts that were **hit**, read
once (an expert no row chose need not be read, and a kernel that skips
it must not be charged for it, or its share would pass 100%), plus each
assignment's row in (``d``), its hidden row out and in again (``f``
twice: two kernels) and its row out (``d``).
"""

from __future__ import annotations

from typing import Dict


def expert_weight_bytes(d_model: int, d_expert: int, itemsize: int = 2
                        ) -> float:
    """One expert's gate, up and down matrices."""
    return 3.0 * d_model * d_expert * itemsize


def expert_kernels_cost(assignments: float, experts_hit: float,
                        d_model: int, d_expert: int, itemsize: int = 2
                        ) -> Dict[str, float]:
    """What the two grouped matmuls need for ``assignments`` rows spread
    over ``experts_hit`` (expert, layer) pairs."""
    rows = 2.0 * assignments * (d_model + d_expert) * itemsize
    return {
        "flops": 6.0 * assignments * d_model * d_expert,
        "bytes": experts_hit * expert_weight_bytes(
            d_model, d_expert, itemsize) + rows,
    }
