"""Autoregressive decoding for the GPT family: KV cache + sampling.

The reference's inference story ends at ``predict_step`` (batch argmax);
a usable LM needs a decode loop.  TPU-first shape discipline throughout:

* **Static shapes**: the KV cache is allocated once at ``total_len`` and
  written with ``lax.dynamic_update_slice`` — no growing arrays, so the
  whole generation is ONE ``lax.scan`` under ``jit`` (no per-token
  retrace, no host round-trips).
* **Stacked layers**: the cache carries a leading ``n_layer`` axis, and
  the per-token block pass is a ``lax.scan`` over (block params, cache
  layer) pairs — same compile-once-per-depth property as the training
  trunk.
* **Fused prefill**: the prompt runs through ONE full-sequence causal
  pass (:func:`prefill`) that writes every prompt slot of the cache in
  a single MXU-friendly batch — the decode scan then covers only the
  new tokens.  Both paths keep the softmax·V product in f32, so they
  match the training forward exactly in f32; under bf16 kernels they
  can differ at near-tie logits (inference is the higher-precision one).
* **Sampling**: greedy, temperature, top-k and nucleus (top-p) — all
  shape-static so the whole generation stays inside one jit.

MoE models decode through the same routed-MLP math as training
(``groups=1``); see :func:`generate` for the capacity-competition
caveat.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.gpt import (
    GPT, GPTConfig, _layer_norm, _mlp_residual, _moe_residual,
)
from ray_lightning_tpu.models.quant import resolve_weight
from ray_lightning_tpu.ops.attention import _NEG_INF

__all__ = ["init_kv_cache", "prefill", "decode_step", "generate"]


def init_kv_cache(
    cfg: GPTConfig, batch: int, total_len: int, dtype=jnp.float32
) -> Dict[str, jax.Array]:
    """(L, B, total_len, H, Dh) zero-filled key/value buffers."""
    shape = (cfg.n_layer, batch, total_len, cfg.n_head, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _block_pass(
    cfg: GPTConfig,
    p: Dict[str, Any],
    x: jax.Array,
    k_l: jax.Array,
    v_l: jax.Array,
    off,
    c,
    ad: Optional[Dict[str, jax.Array]] = None,
    ad_ids: Optional[jax.Array] = None,
    lora_impl: str = "xla",
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One GPT block over ``x (B, T, d)`` against a KV cache layer.

    Writes this chunk's k/v into cache slots ``[off, off + T)`` and
    attends each query ``t`` over cache slots ``<= off + t`` (unwritten
    slots are masked, so their zero-fill never contributes).  The SAME
    code path serves full-prompt prefill (``T = T0, off = 0``) and
    single-token decode (``T = 1, off = pos``) — block math has one
    source, and numerics (f32 scores/softmax/PV) are identical by
    construction.

    ``ad``/``ad_ids`` (multi-tenant LoRA, ``serve/lora.py``): one
    layer's stacked adapter factors plus a per-SEQUENCE int32 slot id
    operand — each row's own adapter delta is added to the qkv/proj
    projections via the gathered BGMV (``ops/lora.py``), slot 0 being
    the zero-delta base model.  ``None`` (every non-serving caller)
    leaves the graph byte-identical to pre-LoRA rounds.
    """
    from ray_lightning_tpu.ops.lora import apply_lora

    B, T = x.shape[0], x.shape[1]
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = h @ resolve_weight(p, "qkv_w", c) + p["qkv_b"].astype(c)
    qkv = apply_lora(qkv, h, ad, "qkv", ad_ids, lora_impl)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(z):
        return z.reshape(B, T, cfg.n_head, cfg.head_dim)

    k_l = jax.lax.dynamic_update_slice(
        k_l, heads(k).astype(k_l.dtype), (0, off, 0, 0)
    )
    v_l = jax.lax.dynamic_update_slice(
        v_l, heads(v).astype(v_l.dtype), (0, off, 0, 0)
    )
    S = k_l.shape[1]
    scale = cfg.head_dim ** -0.5
    scores = jnp.einsum(
        "bqhd,bshd->bhqs", heads(q).astype(jnp.float32),
        k_l.astype(jnp.float32),
    ) * scale
    visible = jnp.arange(S)[None, :] <= (off + jnp.arange(T))[:, None]
    scores = jnp.where(visible[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum(
        "bhqs,bshd->bqhd", probs, v_l.astype(jnp.float32)
    ).reshape(B, T, cfg.d_model).astype(c)
    proj = att @ resolve_weight(p, "proj_w", c) + p["proj_b"].astype(c)
    proj = apply_lora(proj, att, ad, "proj", ad_ids, lora_impl)
    x = x + proj
    if cfg.n_experts > 0:
        # Same routed-MLP math as training (groups=1 — inference is
        # chip-local).  Capacity competition is per ROUTED SET: the full
        # forward routes all B*T prompt tokens together, decode routes
        # the B current tokens — identical decisions whenever capacity
        # doesn't saturate (see generate() docstring).
        x, _ = _moe_residual(x, p, cfg, groups=1)
        return x, k_l, v_l
    return _mlp_residual(x, p, c), k_l, v_l


def _trunk_blocks(cfg, params, cache, x, off, c,
                  adapters=None, adapter_ids=None, lora_impl="xla"):
    """Scan :func:`_block_pass` over the stacked layers; return the
    pre-``ln_f`` hidden for EVERY position and the updated cache.

    The building block shared by :func:`_trunk_pass` (full forward →
    last-position logits) and the serving plane's bucketed prefill
    (``serve/kv_cache.py`` needs the hidden at the last *valid* prompt
    position of a padded bucket, not the last slot).  ``adapters``
    (stacked per-layer LoRA factor buffers, leading axis L) rides the
    scan xs exactly like ``params["blocks"]``; ``None`` keeps the
    graph byte-identical to pre-LoRA rounds (the trace-time unpack is
    the same one-body shape the paged decode/verify programs use)."""

    def block(carry, layer):
        x, = carry
        if adapters is None:
            p, k_l, v_l = layer
            ad = None
        else:
            p, k_l, v_l, ad = layer
        x, k_l, v_l = _block_pass(cfg, p, x, k_l, v_l, off, c,
                                  ad=ad, ad_ids=adapter_ids,
                                  lora_impl=lora_impl)
        return (x,), (k_l, v_l)

    xs = (params["blocks"], cache["k"], cache["v"])
    if adapters is not None:
        xs = xs + (adapters,)
    (x,), (k_new, v_new) = jax.lax.scan(block, (x,), xs)
    return x, {"k": k_new, "v": v_new}


def _head_logits(params, h, c):
    """``ln_f`` + tied LM head on hidden ``(..., d)`` → logits
    ``(..., V)`` f32 (int8-storage aware via :func:`_wte`)."""
    h = _layer_norm(h, params["ln_f_g"], params["ln_f_b"])
    return jnp.einsum(
        "...d,vd->...v", h, _wte(params, c),
        preferred_element_type=jnp.float32,
    )


def _trunk_pass(cfg, params, cache, x, off, c):
    """Scan :func:`_block_pass` over the stacked layers; return the
    final LN'd last-position logits and the updated cache."""
    x, cache = _trunk_blocks(cfg, params, cache, x, off, c)
    return _head_logits(params, x[:, -1], c), cache


def _wte(params, c):
    """Token embedding table in compute dtype (int8-storage aware)."""
    if "wte_q8" in params:
        # Per-row scales broadcast over the feature dim.
        return (params["wte_q8"].astype(c)
                * params["wte_sc"].astype(c)[:, None])
    return params["wte"].astype(c)


def _embed(params, tokens, c):
    """Embedding lookup in compute dtype (int8-storage aware): gather
    the int8 rows, then scale — only the LOOKED-UP rows are converted,
    never the whole table."""
    if "wte_q8" in params:
        return (params["wte_q8"][tokens].astype(c)
                * params["wte_sc"][tokens].astype(c)[..., None])
    return params["wte"][tokens].astype(c)


def _reject_unmerged_lora(params: Dict[str, Any]) -> None:
    """The BASE-model decode math consumes raw ``qkv_w``/``proj_w``
    only; a LoRA-bearing tree passed as the base would silently
    generate from the frozen base weights — the one truly-unsupported
    case, rejected here at every public inference entry (trace-time
    cost only — it inspects dict keys, not values).  Serving adapters
    is supported, just not THIS way: the adapter pool applies them as
    per-slot operands over one resident base (docs/SERVING.md
    "Multi-tenant LoRA")."""
    from ray_lightning_tpu.models.gpt import has_lora_adapters

    if has_lora_adapters(params):
        raise ValueError(
            "params contain LoRA adapters, which the base-model decode "
            "path does not apply — running them would silently generate "
            "from the frozen base weights. Either fold ONE tenant in "
            "(params = merge_lora(params, cfg)) or serve MANY tenants "
            "over the shared base through the adapter pool: "
            "adapter, base = extract_lora(params, cfg); "
            "ServeEngine(module, base, ServeConfig(max_adapters=N, "
            "adapter_rank=cfg.lora_rank), adapters={name: adapter}) — "
            "see docs/SERVING.md 'Multi-tenant LoRA'."
        )


def prefill(
    cfg: GPTConfig,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    compute_dtype=jnp.float32,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence prompt pass: ``tokens (B, T0)`` → ``(last-position
    logits (B, V) f32, cache with slots [0, T0) filled)``.

    One causal-attention batch over the whole prompt instead of ``T0``
    sequential single-token steps — the matmuls stay large for the MXU
    and the cache is written once per layer.
    """
    _reject_unmerged_lora(params)
    c = compute_dtype
    T = tokens.shape[1]
    x = _embed(params, tokens, c) + params["wpe"][:T].astype(c)
    return _trunk_pass(cfg, params, cache, x, 0, c)


def decode_step(
    cfg: GPTConfig,
    params: Dict[str, Any],
    cache: Dict[str, jax.Array],
    tokens: jax.Array,
    pos: jax.Array,
    compute_dtype=jnp.float32,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token per sequence: ``tokens (B,) at position pos`` →
    ``(logits (B, V) f32, updated cache)``."""
    _reject_unmerged_lora(params)
    c = compute_dtype
    x = (_embed(params, tokens, c)
         + params["wpe"][pos].astype(c))[:, None]
    return _trunk_pass(cfg, params, cache, x, pos, c)


def _sample(
    logits: jax.Array,
    rng: jax.Array,
    temperature: float,
    top_k: Optional[int],
    top_p: Optional[float],
) -> jax.Array:
    """One sampling decision per row of ``logits (B, V)`` → ``(B,)``.

    All filtering is shape-static (mask to ``_NEG_INF``, never shrink the
    vocab axis) so the caller's scan stays a single compiled program.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(
            logits, min(top_k, logits.shape[-1])
        )[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p is not None:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep tokens whose EXCLUSIVE cumulative mass is < top_p: the
        # nucleus always includes the top token and stops once the kept
        # mass first reaches top_p.
        keep = (cum - probs) < top_p
        num_keep = keep.sum(axis=-1, keepdims=True)
        thresh = jnp.take_along_axis(sorted_desc, num_keep - 1, axis=-1)
        logits = jnp.where(logits < thresh, _NEG_INF, logits)
    return jax.random.categorical(rng, logits)


def generate(
    module: GPT,
    params: Dict[str, Any],
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    rng: Optional[jax.Array] = None,
    eos_token_id: Optional[int] = None,
) -> jax.Array:
    """Greedy (``temperature=0``), temperature, top-k and/or top-p
    (nucleus) sampling.  Prompt slots fill via one fused :func:`prefill`
    pass; the decode scan covers only the new tokens.

    Args:
        prompt: ``(B, T0)`` int32, ``T0 >= 1``.
        top_k: keep only the k highest-probability tokens (``>= 1``).
        top_p: keep the smallest set of tokens whose probability mass
            reaches ``top_p`` (``0 < top_p <= 1``).  Composes with
            ``top_k`` (k-filter first, as in the usual HF semantics).
        rng: sampling key.  Defaults to ``PRNGKey(0)`` — deterministic,
            so repeated calls return the SAME sample; pass a fresh key
            per call for diverse samples.
        eos_token_id: once a sequence samples this token every later
            position repeats it (the sequence is *finished*).  Shapes
            stay static under jit — the scan still runs ``max_new_tokens``
            steps — but finished rows stop changing, the standard
            XLA-friendly stopping semantics.

    MoE models decode with the same routed-MLP math as training
    (``groups=1``).  Caveat: expert-capacity competition happens per
    routed set — training/prefill routes a whole ``(B, T)`` batch while
    decode routes the ``B`` current tokens — so token drops can differ
    when capacity saturates; with headroom
    (``capacity_factor >= n_experts`` guarantees zero drops) decode
    matches the full forward exactly (tested).
    Returns:
        ``(B, T0 + max_new_tokens)`` int32 — prompt followed by the
        generated continuation.
    """
    cfg = module.config
    _reject_unmerged_lora(params)
    B, t0 = prompt.shape
    if t0 < 1:
        raise ValueError("prompt must contain at least one token")
    if max_new_tokens < 0:
        raise ValueError(f"max_new_tokens must be >= 0, got {max_new_tokens}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if (top_k is not None or top_p is not None) and temperature <= 0.0:
        raise ValueError(
            "top_k/top_p require temperature > 0 (temperature=0 is "
            "greedy decoding, which would silently ignore them)"
        )
    if (eos_token_id is not None
            and not 0 <= eos_token_id < cfg.vocab_size):
        raise ValueError(
            f"eos_token_id {eos_token_id} outside vocab "
            f"[0, {cfg.vocab_size}) — stopping would silently never "
            f"trigger"
        )
    total = t0 + max_new_tokens
    if total > cfg.seq_len:
        raise ValueError(
            f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"the positional table ({cfg.seq_len})"
        )
    # Accept host pytrees (e.g. ``trainer.params``) as well as device
    # arrays: numpy leaves cannot be gather-indexed by traced tokens.
    params = jax.tree.map(jnp.asarray, params)
    # Int8 weight-only storage pays off where decode is HBM-bandwidth
    # bound (TPU: int8 is what HBM streams, the convert fuses into the
    # matmul).  Off-TPU the per-token dequant inside the decode scan
    # COSTS more than the bandwidth it saves (a CPU run: 3345.7 int8 vs
    # 4025.3 fp tokens/s), so hoist it: dequantize ONCE per call,
    # outside the scan — same math, amortized over every generated
    # token.
    from ray_lightning_tpu.models.quant import (
        dequantize_decode_params, is_quantized,
    )

    if is_quantized(params) and jax.default_backend() != "tpu":
        params = dequantize_decode_params(params)
    prompt = jnp.asarray(prompt).astype(jnp.int32)
    if max_new_tokens == 0:
        return prompt
    c = module._compute_dtype()
    cache = init_kv_cache(cfg, B, total, dtype=c)
    if rng is None:
        rng = jax.random.PRNGKey(0)

    logits, cache = prefill(cfg, params, cache, prompt, compute_dtype=c)
    rng, sub = jax.random.split(rng)
    first = _sample(logits, sub, temperature, top_k, top_p)
    first = first.astype(jnp.int32)
    done0 = (
        first == eos_token_id if eos_token_id is not None
        else jnp.zeros((B,), bool)
    )

    def step(carry, t):
        cache, cur, rng, done = carry
        logits, cache = decode_step(
            cfg, params, cache, cur, t, compute_dtype=c
        )
        rng, sub = jax.random.split(rng)
        nxt = _sample(logits, sub, temperature, top_k, top_p)
        nxt = nxt.astype(jnp.int32)
        if eos_token_id is not None:
            # Finished rows keep emitting eos; the row freezes.
            nxt = jnp.where(done, jnp.int32(eos_token_id), nxt)
            done = done | (nxt == eos_token_id)
        return (cache, nxt, rng, done), nxt

    # Positions t0 .. total-2 emit tokens t0+1 .. total-1.
    (_, _, _, _), rest = jax.lax.scan(
        step, (cache, first, rng, done0), jnp.arange(t0, total - 1)
    )
    return jnp.concatenate([prompt, first[:, None], rest.T], axis=1)
