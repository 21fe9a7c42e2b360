"""Driver ``serve_mimo_closed``: the closed loop of ``lib/serve_share.py``
for the ``mimo_v2`` family (``ray_lightning_tpu/models/mimo_v2.py``),
held to ``benchmarks/reference/mimo_v2_ref.py``.

The loop, the window by count and the check are ``serve_share``'s; this
file names the family, its limits and the shapes its roofline reader
reads.  Set-up:

* weights are made on the device in bf16 by the module, a layer and an
  expert at a time;
* the engine's programs are warmed by one request per prefill bucket,
  alone and then all at once;
* what those requests were SERVED (prefill, then decode through the
  two-kind pool: the full layers by ``rlt_paged_decode``, the window
  layers' rings by the XLA gather with the sink) is held to the float32
  reference run along the served sequences (logits, not tokens); the
  program's own forward, its router scores and its expert choices on the
  shortest are held to the reference's;
* the window opens when the callers have taken ``lead_in_blocks`` whole
  blocks of the traffic (64 requests in the cell: 32 completions); after
  it the longest and the shortest request it served from start to end
  are held to the reference too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib import harness, serve_share
from benchmarks.reference import mimo_v2_ref

# Largest reading of the program over 14 seeds / smallest of the float8
# reference over the same (TPU v5e; PERF.md section 6); each limit
# near the geometric mean of its two readings, the float8 reference
# outside all four:
LIMITS = {
    "mean_gap": 0.01,        # 0.00221 / 0.0661
    "logit_rms": 0.07,       # 0.0220 / 0.247
    "router_score": 0.07,    # 0.0161 / 0.290
    "flip_margin": 0.012,    # 0.0044 / 0.0245
}


def run(run: harness.Run) -> Dict[str, Any]:
    # The family's program first: a tree without it fails here, before
    # the chip is touched.
    from ray_lightning_tpu.models.exaone_moe import (
        head_logits, sequence_forward,
    )
    from ray_lightning_tpu.models.mimo_v2 import MiMoV2, MiMoV2Config

    import jax.numpy as jnp

    fields = dict(run.config_fields())
    for key in ("experts_held", "vocab_held"):
        if fields.get(key) is not None:
            fields[key] = tuple(fields[key])
    cfg = MiMoV2Config(**fields)
    first_sparse = cfg.mlp_types.index("sparse")
    full = cfg.geometry("full")
    return serve_share.run(run, serve_share.Family(
        cfg=cfg, module=MiMoV2(cfg), ref=mimo_v2_ref,
        sequence_forward=sequence_forward, head_logits=head_logits,
        selection_bias=lambda p: p["layers"][first_sparse]["router_bias"],
        limits=LIMITS,
        obs={"paged": {"n_head": cfg.n_head, "n_kv_head": full.n_kv_head,
                       "head_dim": full.head_dim,
                       "v_head_dim": full.v_head_dim,
                       "layers": len(cfg.layers_of("full")),
                       "itemsize": jnp.dtype(cfg.param_dtype).itemsize}}))
