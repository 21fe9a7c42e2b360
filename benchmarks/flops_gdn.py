"""Operations and bytes of the gated delta rule's decode step
(``ops/gated_delta.py`` kernel ``rlt_gdn_decode``), from shapes and the
program's own counters.

One slot's step of one linear layer reads each of its ``Hv`` value
heads' float32 state ``(K, V)`` once and writes it back once, and moves
the step's inputs in (q and k: ``Hk`` heads of ``K``; v: ``Hv`` of
``V``; the decay and the write strength: ``Hv`` each, all float32) and
its output out (``Hv`` of ``V`` float32).  Per value head it decays the
state (``K V`` multiplies), reads ``S^T k`` (``2 K V``), adds the rank-1
correction (``2 K V``) and reads ``S^T q`` (``2 K V``): ``7 K V``
FLOPs against ``8 K V`` bytes of state, so the bytes bind on any chip.
The lanes the kernel spends on broadcast gates are its to lose: not
need.
"""

from __future__ import annotations

from typing import Dict


def gdn_decode_cost(slot_layers: float, n_value_heads: int,
                    n_key_heads: int, key_dim: int, value_dim: int
                    ) -> Dict[str, float]:
    """What one tick's recurrence needs: ``slot_layers`` (active slots x
    linear layers) steps of one slot's state."""
    state = n_value_heads * key_dim * value_dim
    io = (2 * n_key_heads * key_dim + 2 * n_value_heads * value_dim
          + 2 * n_value_heads) * 4
    return {
        "flops": 7.0 * state * slot_layers,
        "bytes": slot_layers * (2 * state * 4 + io),
    }
