"""Operations and bytes of the latent (MLA) decode attention
(``ops/paged_attention.py`` kernel ``rlt_mla_decode``), from shapes and
the program's own counters.

A cached position of one layer is one row ``[c | k_r]`` of ``r + dr``
numbers shared by all ``H`` heads.  Attending it costs, per head, a
score (``r + dr`` multiply-adds against the absorbed query) and a value
accumulation (``r``): ``2 H ((r + dr) + r)`` FLOPs.  The least HBM
traffic reads the row once, ``(r + dr) * itemsize`` bytes, for scores
and values alike, plus, per slot and layer, the absorbed queries in
(``H (r + dr)``), the current token's own row in (``r + dr``) and the
output out (``H r`` float32).  The padding lanes of the pool's rows and
the tail of a slot's last block are the kernel's to lose: not need.
"""

from __future__ import annotations

from typing import Dict


def mla_decode_cost(positions: float, slot_layers: float, n_head: int,
                    rank: int, rope_dim: int, itemsize: int = 2
                    ) -> Dict[str, float]:
    """What one tick's latent decode needs: ``positions`` attended
    (summed over slots and layers, each slot's own token included) by
    ``slot_layers`` kernel programs (active slots x latent layers)."""
    row = rank + rope_dim
    io = (n_head * row + row) * itemsize + n_head * rank * 4
    return {
        "flops": 2.0 * n_head * (row + rank) * positions,
        "bytes": positions * row * itemsize + slot_layers * io,
    }
