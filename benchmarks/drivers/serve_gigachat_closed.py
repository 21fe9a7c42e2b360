"""Driver ``serve_gigachat_closed``: the closed loop of
``lib/serve_share.py`` for the ``gigachat35`` family
(``ray_lightning_tpu/models/gigachat35.py``), held to
``benchmarks/reference/gigachat35_ref.py``.

The loop, the window by count and the check are ``serve_share``'s; this
file names the family, its limits and the shapes its roofline readers
read.  Set-up:

* weights are made on the device in bf16 by the module, a layer and an
  expert at a time; then each routed layer's selection bias is set as
  a trained ``noaux_tc`` router's is, to even out the experts' loads
  (``balanced_bias``) on the program's own scores over seeded sequences
  of the traffic's kind (``router_balance``): drawn weights alone route
  each tick's rows to a few experts picked by a direction that every
  long context shares, which ones depending on the seed;
* the engine's programs are warmed by one request per prefill bucket,
  alone and then all at once, each decoding ``warmup_new_tokens``
  tokens (560 in the cell: the reference's longer width is then 2560
  positions, and the traffic's requests that come nearest it without
  passing it each decode over a thousand tokens through the state, so
  the window's longest sample does);
* what those requests were SERVED (prefill, then decode through the
  latent pool by ``rlt_mla_decode`` and through the slots' recurrent
  state by ``rlt_gdn_decode``) is held to the float32 reference run
  along the served sequences, its linear layers token by token (logits,
  not tokens); the program's own forward, its router scores and its
  expert choices on the shortest are held to the reference's;
* the window opens when the callers have taken ``lead_in_blocks`` whole
  blocks of the traffic (192 requests in the cell: 64 completions); after
  it the longest and the shortest request it served from start to end
  are held to the reference too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib import harness, serve_share
from benchmarks.reference import gigachat35_ref

# Steps of the balancing rule, and its rate in units of the scores'
# spread at the first step and the last.
BALANCE_STEPS = 400
BALANCE_RATE = (0.5, 0.025)

# Largest reading of the program over the seeds / smallest of the
# float8 reference over the same (TPU v5e; PERF.md section 6):
LIMITS = {
    "mean_gap": 0.05,
    "logit_rms": 0.1,
    "router_score": 0.07,
    "flip_margin": 0.015,
}


def balanced_bias(z, bias, top_k: int):
    """The selection bias under which the ``top_k`` of ``z + bias`` give
    every expert the same share of the rows of ``z`` (S, E): the rule a
    ``noaux_tc`` router learns its bias by (an expert above its share is
    biased down, one below it up, in proportion), run to rest on fixed
    scores from ``bias``."""
    import jax
    import jax.numpy as jnp

    n_exp = z.shape[-1]
    share = z.shape[0] * top_k / n_exp
    spread = jnp.std(z, axis=0).mean()
    first, last = BALANCE_RATE

    def step(i, b):
        _, idx = jax.lax.top_k(z + b, top_k)
        load = jnp.zeros((n_exp,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        rate = first + (last - first) * i / (BALANCE_STEPS - 1)
        return b + rate * spread * (share - load) / share

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             bias.astype(jnp.float32))


def expert_loads(z, bias, top_k: int, held: int):
    """``(the largest and the smallest expert's share of the rows' choices
    over the mean share, the held experts' share in %)``."""
    import jax
    import numpy as np

    idx = np.asarray(jax.lax.top_k(z + bias, top_k)[1])
    load = np.bincount(idx.reshape(-1), minlength=z.shape[-1])
    mean = load.mean()
    return (float(load.max() / mean), float(load.min() / mean),
            float(100 * (idx < held).mean()))


def balance_routers(cfg, module, params, key, sequences: int, width: int,
                    skip: int, note):
    """``params`` with each routed layer's ``router_bias`` balanced
    (``balanced_bias``) on the scores the program's own forward gives at
    positions ``skip`` to ``width`` of ``sequences`` seeded sequences of
    the held vocabulary: far enough in that attention and the recurrent
    state average a long context, as they do at decode.  One pass: the
    later layers' scores are taken under the earlier layers' drawn
    biases."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gigachat35 import sequence_forward

    sparse = [i for i, m in enumerate(cfg.mlp_types) if m == "sparse"]
    held = cfg.n_experts_held

    @jax.jit
    def scores(p, toks):
        routing: list = []
        sequence_forward(cfg, p, toks[None], attn_impl=module.attn_impl,
                         moe_impl=module.moe_impl, routing=routing)
        return [z[skip:] for _, z in routing]

    toks = jax.random.randint(key, (sequences, width), 1, cfg.n_vocab_held)
    per_seq = [scores(params, toks[j]) for j in range(sequences)]
    balance = jax.jit(balanced_bias, static_argnums=(2,))
    layers = list(params["layers"])
    loads = {}
    for n, i in enumerate(sparse):
        z = jnp.concatenate([zs[n] for zs in per_seq])
        drawn = layers[i]["router_bias"]
        bias = balance(z, drawn, cfg.top_k)
        layers[i] = dict(layers[i], router_bias=bias)
        loads[i] = {"drawn": expert_loads(z, drawn, cfg.top_k, held),
                    "balanced": expert_loads(z, bias, cfg.top_k, held)}
    note(phase="router_balance", rows=sequences * (width - skip),
         loads_max_min_held_pct=loads)
    return dict(params, layers=layers)


def run(run: harness.Run) -> Dict[str, Any]:
    # The family's program first: a tree without it fails here, before
    # the chip is touched.
    from ray_lightning_tpu.models.exaone_moe import head_logits
    from ray_lightning_tpu.models.gigachat35 import (
        GigaChat35, GigaChat35Config, sequence_forward,
    )

    import jax
    import jax.numpy as jnp

    fields = dict(run.config_fields())
    for key in ("experts_held", "vocab_held", "full_layers"):
        if fields.get(key) is not None:
            fields[key] = tuple(fields[key])
    cfg = GigaChat35Config(**fields)
    first_sparse = cfg.mlp_types.index("sparse")
    calibration = run.system()["router_balance"]

    class Balanced(GigaChat35):
        def init_params(self, rng):
            return balance_routers(
                cfg, self, super().init_params(rng),
                jax.random.fold_in(rng, 1), note=run.note, **calibration)

    return serve_share.run(run, serve_share.Family(
        cfg=cfg, module=Balanced(cfg), ref=gigachat35_ref,
        sequence_forward=sequence_forward, head_logits=head_logits,
        selection_bias=lambda p: p["layers"][first_sparse]["router_bias"],
        limits=LIMITS,
        obs={"mla": {"n_head": cfg.n_head, "rank": cfg.kv_lora_rank,
                     "rope_dim": cfg.qk_rope_head_dim,
                     "layers": len(cfg.layers_of("latent")),
                     "itemsize": jnp.dtype(cfg.param_dtype).itemsize},
             "gdn": {"n_value_heads": cfg.lin_value_heads,
                     "n_key_heads": cfg.lin_key_heads,
                     "key_dim": cfg.lin_key_dim,
                     "value_dim": cfg.lin_value_dim,
                     "layers": len(cfg.layers_of("linear"))}}))
