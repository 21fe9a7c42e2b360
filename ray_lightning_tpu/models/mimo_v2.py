"""The ``mimo_v2`` family: MiMo-V2-Flash's decoder on the pattern
decoder of ``models/exaone_moe.py``.

Its two mixer kinds are exaone's, ``full`` and ``sliding``, each with an
attention geometry of its own (:class:`~ray_lightning_tpu.models.
exaone_moe.AttnGeometry`), which is all that differs in the code:

* ``full``: 64 query heads over 4 K/V heads, rotary theta 5e6, causal;
* ``sliding``: 64 over 8 K/V heads, theta 1e4, a window of 128 and a
  learned sink logit per query head (a key with no value);
* both: queries and keys 192 wide of which the first 64 rotate
  (``partial_rotary_factor``), values 128 wide and scaled by 0.707
  (``attention_value_scale``).

The block is pre-norm (``x + Attn(norm(x))``, ``x + F(norm(x))``) with
no q/k norm; layer 0 is a dense SiLU-gated MLP, every other layer
sigmoid-scored top-8 routing chosen by ``score + bias`` over 256 experts
with no shared expert and no routed scale.  Everything else, the serving
cache (K and V rows of each kind's own widths), the prefill and decode
programs and the share (``experts_held``, ``vocab_held``), is
``exaone_moe``'s.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.exaone_moe import (
    AttnGeometry, ExaoneMoE, ExaoneMoEConfig,
    ServeFamily as ExaoneServeFamily, init_tree,
)

__all__ = ["MiMoV2", "MiMoV2Config", "mimo_v2_tiny"]

FAMILY = "mimo_v2"


@dataclasses.dataclass(frozen=True)
class MiMoV2Config(ExaoneMoEConfig):
    """The model's shape (``config.json``'s sizes under the pattern
    decoder's names) and this chip's share.  ``n_kv_head`` and
    ``rope_theta`` are the full layers'; the sliding layers' are
    ``swa_*``."""

    vocab_size: int = 152576
    d_model: int = 4096
    n_layer: int = 48
    n_head: int = 64
    n_kv_head: int = 4
    head_dim: int = 192           # query and key columns, both kinds
    d_ff: int = 16384
    d_expert: int = 2048
    n_experts: int = 256
    top_k: int = 8
    routed_scale: float = 1.0     # routed_scaling_factor: null
    window: int = 128
    rms_eps: float = 1e-5
    rope_theta: float = 5e6
    seq_len: int = 262144
    v_head_dim: int = 128
    rope_dim: int = 64            # int(192 * partial_rotary_factor 0.334)
    value_scale: float = 0.707
    swa_n_kv_head: int = 8
    swa_rope_theta: float = 1e4

    norms = "pre"
    shared_expert = False         # n_shared_experts: null

    def __post_init__(self):
        if self.layer_types is None:
            # hybrid_layer_pattern: full at 0 and every sixth from 5.
            object.__setattr__(self, "layer_types", tuple(
                "full" if i == 0 or i % 6 == 5 else "sliding"
                for i in range(self.n_layer)))
        super().__post_init__()
        if self.n_head % self.swa_n_kv_head:
            raise ValueError("n_head must be a multiple of swa_n_kv_head")
        if self.rope_dim % 2 or not 0 < self.rope_dim <= self.head_dim:
            raise ValueError("rope_dim must be even and within head_dim")

    def geometry(self, kind: str) -> AttnGeometry:
        """Sinks on the window layers only (``add_swa_attention_sink_bias``
        true, ``add_full_attention_sink_bias`` false)."""
        if kind == "full":
            return AttnGeometry(
                self.n_kv_head, self.head_dim, self.v_head_dim,
                self.rope_dim, self.rope_theta, window=None,
                value_scale=self.value_scale)
        return AttnGeometry(
            self.swa_n_kv_head, self.head_dim, self.v_head_dim,
            self.rope_dim, self.swa_rope_theta, window=self.window,
            sink=True, value_scale=self.value_scale)


def mimo_v2_tiny(**over) -> MiMoV2Config:
    """The CPU tests' preset: the published pattern's first seven layers
    (``F S S S S F S``, layer 0 dense), 16 experts top-4, window 8, 8
    query heads over 2 (full) and 4 (sliding) K/V heads, keys 12 wide of
    which 4 rotate, values 8 wide, every width tiny."""
    base = dict(vocab_size=256, d_model=32, n_layer=7, n_head=8,
                n_kv_head=2, swa_n_kv_head=4, head_dim=12, v_head_dim=8,
                rope_dim=4, d_ff=64, d_expert=16, n_experts=16, top_k=4,
                window=8, seq_len=256, param_dtype="float32")
    base.update(over)
    return MiMoV2Config(**base)


class ServeFamily(ExaoneServeFamily):
    """``exaone_moe``'s seam (two kinds of cache state, the same
    programs) under this family's name."""

    name = FAMILY


class MiMoV2(ExaoneMoE):
    """``TpuModule`` of the family: ``ExaoneMoE``'s trunk, loss and
    serving programs over this family's geometry and weights."""

    # The selection bias is a buffer of the published model (noaux_tc),
    # drawn from the seed so that selection and weighting differ in a
    # run; the sinks are drawn wide enough to take a visible share of
    # each window's probability.
    ROUTER_BIAS_STD = 0.01
    SINK_STD = 1.0

    def serve_family(self) -> ServeFamily:
        return ServeFamily(self)

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        d, H = cfg.d_model, cfg.n_head

        def mixer_leaves(w, ks, kind):
            g = cfg.geometry(kind)
            p = {"wq": w(ks[0], (d, H * g.head_dim)),
                 "wk": w(ks[1], (d, g.k_width)),
                 "wv": w(ks[2], (d, g.v_width)),
                 "wo": w(ks[3], (H * g.v_head_dim, d)),
                 "attn_norm": jnp.ones((d,), jnp.float32),
                 "ffn_norm": jnp.ones((d,), jnp.float32)}
            if g.sink:
                p["sink"] = jax.random.normal(
                    jax.random.fold_in(ks[0], 1), (H,),
                    jnp.float32) * self.SINK_STD
            return p

        return init_tree(cfg, rng, mixer_leaves,
                         router_bias_std=self.ROUTER_BIAS_STD)
