"""Weight-only int8 quantization for the decode path.

Single-token decode is HBM-bandwidth-bound: every generated token
re-reads every weight matrix, so weight bytes ARE the decode cost.
Symmetric per-output-channel int8 storage halves the weight traffic vs
bf16 (4x vs f32) while activations, cache, and all math stay in the
compute dtype — XLA fuses the ``int8 -> compute-dtype`` convert and the
per-channel scale into the matmul's operand read, so no dequantized
copy of the weights ever lands in HBM.

Scope: inference only.  ``quantize_decode_params`` produces a tree the
generation path (``models/generate.py``) consumes transparently — a
quantized weight ``w`` is stored as ``w_q8`` (int8) + ``w_sc`` (f32
per-output-channel scales) and resolved by :func:`resolve_weight`.
Warm-starting a fit from such a tree (``module.initial_params``) is
rejected with a clear error — the optimizer cannot step int8 storage,
and silently dequantizing would train an already-rounded model.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "quantize_decode_params",
    "dequantize_decode_params",
    "resolve_weight",
    "is_quantized",
]

# Weights worth quantizing: the 2-D+ matmul operands.  Biases, LN
# params, and the positional table stay f32 (tiny, and bias precision
# is cheap accuracy).
_QUANT_BLOCK_KEYS = ("qkv_w", "proj_w", "mlp_in_w", "mlp_out_w")


def _quantize(w: jax.Array, contract_axis: int) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 over ``contract_axis`` (the input/contraction dim):
    one f32 scale per OUTPUT channel, so the matmul result is exact up
    to the 8-bit mantissa of each channel."""
    amax = jnp.max(jnp.abs(w), axis=contract_axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis=contract_axis)


def is_quantized(params: Dict[str, Any]) -> bool:
    return any(
        str(k).endswith("_q8") for k in params.get("blocks", {})
    ) or "wte_q8" in params


def resolve_weight(tree: Dict[str, Any], name: str, compute_dtype):
    """``tree[name]`` in ``compute_dtype`` — dequantizing on the fly when
    the tree carries int8 storage.  The convert+scale fuses into the
    consuming matmul; int8 is what HBM streams."""
    q = tree.get(name + "_q8")
    if q is None:
        return tree[name].astype(compute_dtype)
    sc = tree[name + "_sc"].astype(compute_dtype)
    # Scales are per OUTPUT channel; re-insert the contraction axis so
    # they broadcast against (…, d_in, d_out) storage of any rank
    # (plain (d,k), stacked (L,d,k), expert-stacked (L,E,d,h)).
    return q.astype(compute_dtype) * sc[..., None, :]


def quantize_decode_params(
    params: Dict[str, Any], cfg
) -> Dict[str, Any]:
    """Int8-storage copy of a GPT param tree for generation.

    Block matmul weights quantize per output channel over the
    contraction dim; ``wte`` quantizes per vocab ROW (correct for both
    the embedding lookup and the tied LM-head contraction, which reduce
    over d_model).  Everything else passes through.  LoRA trees must be
    merged first (adapters would silently be dropped otherwise).

    ``cfg`` is currently unused — which weights quantize is keyed on
    TREE contents, never config (a cfg/tree mismatch must not skip
    weights) — but stays in the signature for symmetry with the other
    param-tree transforms (``merge_lora``/``add_lora_adapters``) and
    future config-dependent choices (e.g. per-family bit widths).
    """
    from ray_lightning_tpu.models.gpt import has_lora_adapters

    if has_lora_adapters(params):
        raise ValueError(
            "params contain LoRA adapters; merge_lora(params, cfg) "
            "before quantizing for decode"
        )
    if is_quantized(params):
        raise ValueError("params are already int8-quantized")
    blocks = dict(params["blocks"])
    # Keyed on TREE contents, not cfg: a cfg/tree mismatch must never
    # silently leave the dominant (expert) weights unquantized.
    quant_keys = _QUANT_BLOCK_KEYS + ("moe_in_w", "moe_out_w")
    for key in quant_keys:
        if key not in blocks:
            continue
        w = blocks.pop(key)
        # Leading dims (layer L, expert E) are per-matrix; the
        # contraction dim is axis -2 for every (…, d_in, d_out) weight.
        q, sc = _quantize(jnp.asarray(w), contract_axis=-2)
        blocks[key + "_q8"] = q
        blocks[key + "_sc"] = sc
    out = {**params, "blocks": blocks}
    wte = out.pop("wte")
    # Per-row scales: both consumers (lookup, tied-head einsum over d)
    # contract/select over the feature dim, never across rows.
    q, sc = _quantize(jnp.asarray(wte), contract_axis=-1)
    out["wte_q8"] = q
    out["wte_sc"] = sc
    return out


def dequantize_decode_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Fold int8 storage back into dense f32 weights.

    Exactly the :func:`resolve_weight` / ``_wte`` arithmetic, applied
    ONCE instead of at every consumption site.  Used by the generation
    path to hoist the dequant out of the decode scan on backends where
    weight bytes are not the decode bottleneck (CPU: the per-token
    ``int8 → f32`` convert costs more than the bandwidth it saves — a
    CPU run measured the int8 tree 17% SLOWER there).  The rounding
    already baked into the int8 storage is kept — this is a placement
    change, not a precision change.
    """
    if not is_quantized(params):
        return params
    blocks = dict(params["blocks"])
    for key in [k for k in blocks if str(k).endswith("_q8")]:
        base = key[: -len("_q8")]
        q = blocks.pop(key)
        sc = blocks.pop(base + "_sc")
        blocks[base] = q.astype(jnp.float32) * sc[..., None, :]
    out = {
        k: v for k, v in params.items()
        if k not in ("blocks", "wte_q8", "wte_sc")
    }
    out["blocks"] = blocks
    if "wte_q8" in params:
        out["wte"] = (params["wte_q8"].astype(jnp.float32)
                      * params["wte_sc"].astype(jnp.float32)[:, None])
    return out
