"""Driver ``serve_mla_closed``: the closed loop of ``lib/serve_share.py``
for the ``sarvam_mla`` family (``ray_lightning_tpu/models/sarvam_mla.py``),
held to ``benchmarks/reference/sarvam_mla_ref.py``.

The loop, the window by count and the check are ``serve_share``'s (which
imports ``serve_closed``'s callers and ``serve_moe_closed``'s counter);
this file names the family and its limits.  Set-up:

* weights are made on the device in bf16 by the module, a layer and an
  expert at a time; the engine rearranges ``wkvb`` once;
* the engine's programs are warmed by one request per prefill bucket,
  alone and then all at once;
* what those requests were SERVED (prefill un-absorbed, then decode
  through the latent cache in the absorbed form) is held to the float32
  reference run along the served sequences (logits, not tokens), one of
  them past the original 4096 positions that YaRN stretches; the
  program's own forward, its router scores and its expert choices on
  the two shortest are held to the reference's;
* the window opens when the callers have taken ``lead_in_blocks`` whole
  blocks of the traffic (128 requests in the cell: 64 completions, about
  one mean request's life, so that the slots' ages are mixed when timing
  starts); after it the longest and the shortest request it served from
  start to end are held to the reference too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib import harness, serve_share
from benchmarks.reference import sarvam_mla_ref

# Largest reading of the program over 28 seeds / smallest of the float8
# reference over the same (my chip runs, PR 30; PERF.md section 6); each
# limit near the geometric mean of its two readings:
LIMITS = {
    "mean_gap": 0.07,        # 0.0195 (warm-up; 0.0093 with the window's
    #                          sample counted in) / 0.254
    "logit_rms": 0.15,       # 0.0612 / 0.362
    "router_score": 0.07,    # 0.0191 / 0.279
    "flip_margin": 0.02,     # 0.0085 / 0.0394
}


def run(run: harness.Run) -> Dict[str, Any]:
    import jax.numpy as jnp

    from ray_lightning_tpu.models.exaone_moe import head_logits
    from ray_lightning_tpu.models.sarvam_mla import (
        SarvamMLA, SarvamMLAConfig, sequence_forward,
    )

    fields = dict(run.config_fields())
    for key in ("experts_held", "vocab_held"):
        if fields.get(key) is not None:
            fields[key] = tuple(fields[key])
    cfg = SarvamMLAConfig(**fields)
    return serve_share.run(run, serve_share.Family(
        cfg=cfg, module=SarvamMLA(cfg), ref=sarvam_mla_ref,
        sequence_forward=sequence_forward, head_logits=head_logits,
        selection_bias=lambda p: p["layers"][cfg.first_dense]["router_bias"],
        limits=LIMITS,
        obs={"mla": {"n_head": cfg.n_head, "rank": cfg.kv_lora_rank,
                     "rope_dim": cfg.qk_rope_head_dim, "layers": cfg.n_layer,
                     "itemsize": jnp.dtype(cfg.param_dtype).itemsize}}))
