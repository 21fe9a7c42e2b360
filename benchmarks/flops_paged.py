"""Operations and bytes of grouped paged decode attention
(``ops/paged_attention.py`` kernel ``rlt_paged_decode`` with fewer K/V
heads than query heads), from shapes and the program's own counters.

A cached position of one layer is a K row of ``Hkv * Dh`` numbers and a
V row of ``Hkv * Dv``, read once for all ``H`` query heads.  Attending
it costs, per query head, a score (``Dh`` multiply-adds) and a value
accumulation (``Dv``): ``2 H (Dh + Dv)`` FLOPs.  The least HBM traffic
reads both rows once, ``Hkv (Dh + Dv) * itemsize`` bytes, plus, per slot
and layer, the queries in (``H Dh``) and the output out (``H Dv``
float32).  The positions counted include each slot's own token, whose
rows arrive beside the queries.  Positions the kernel computes past a
slot's last (the tail of its last sub-tile) are the kernel's to lose:
not need.
"""

from __future__ import annotations

from typing import Dict


def paged_decode_cost(positions: float, slot_layers: float, n_head: int,
                      n_kv_head: int, head_dim: int, v_head_dim: int,
                      itemsize: int = 2) -> Dict[str, float]:
    """What one tick's grouped paged decode needs: ``positions``
    attended (summed over slots and layers) by ``slot_layers`` kernel
    programs (active slots x layers)."""
    row = n_kv_head * (head_dim + v_head_dim) * itemsize
    io = n_head * head_dim * itemsize + n_head * v_head_dim * 4
    return {
        "flops": 2.0 * n_head * (head_dim + v_head_dim) * positions,
        "bytes": positions * row + slot_layers * io,
    }
