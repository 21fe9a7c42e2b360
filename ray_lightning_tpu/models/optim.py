"""Shared optimizer helpers for the in-framework model families.

Besides the weight-decay mask, this module owns the **optimizer-state
precision policy** (``GPTConfig.opt_state_dtype`` /
``ViTConfig.opt_state_dtype``): :func:`quantize_opt_state` wraps any
adam-family ``optax.GradientTransformation`` so its moments are STORED
in bf16 or block-scaled int8 (``ops/optim_quant.py``) while the update
math stays f32 — dequant → f32 update → requant runs inside the donated
train step, so the f32 moments never persist in HBM.
:func:`opt_state_bytes` is the analytic accounting of that state's
bytes (``tests/test_opt_state.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from ray_lightning_tpu.ops.optim_quant import (
    DEFAULT_BLOCK_SIZE,
    MIN_QUANT_SIZE,
    BlockQuantized,
    dequantize_moment,
    is_block_quantized,
    quantize_moment,
)

__all__ = [
    "decay_mask",
    "OPT_STATE_DTYPES",
    "resolve_opt_state_dtype",
    "quantize_opt_state",
    "apply_opt_state_dtype",
    "opt_state_bytes",
]

# Matrix-valued params by naming convention (GPT/ViT family): ``*_w``
# projections, plus the token embedding (tied to the LM head — it IS the
# output matrix).  Everything else — biases (``*_b``), LayerNorm gains
# (``*_g``), positional tables (``wpe``/``pos``) — is exempt, in both
# families.
_DECAY_EXACT = {"wte"}


def decay_mask(params: Dict[str, Any]):
    """AdamW weight-decay mask: decay matmul weights, never LayerNorm
    params or biases.

    Keyed on the family's naming convention rather than ndim: stacked
    blocks carry a leading layer dim and MoE tensors an expert dim, so
    a per-block MoE bias is 3-D while still being a bias — any raw
    ``ndim > k`` rule misclassifies one group or another.
    """

    def rule(path, leaf):
        name = getattr(path[-1], "key", "") if path else ""
        return name.endswith("_w") or name in _DECAY_EXACT

    return jax.tree_util.tree_map_with_path(rule, params)


# -- optimizer-state precision ------------------------------------------------

# ``None`` is a valid resolved value: "no policy" — the family keeps its
# legacy behavior (GPT: bf16 first moment via optax's ``mu_dtype``,
# which the explicit "bfloat16" policy generalizes to BOTH moments).
OPT_STATE_DTYPES = ("float32", "bfloat16", "int8")

_OPT_DTYPE_ALIASES = {
    "f32": "float32", "fp32": "float32",
    "bf16": "bfloat16",
}


def resolve_opt_state_dtype(value: Optional[str]) -> Optional[str]:
    """Normalize an ``opt_state_dtype`` knob value; typos fail loudly at
    optimizer construction, not minutes into a fit."""
    if value is None:
        return None
    name = _OPT_DTYPE_ALIASES.get(str(value), str(value))
    if name not in OPT_STATE_DTYPES:
        raise ValueError(
            f"opt_state_dtype {value!r} not in {OPT_STATE_DTYPES} "
            f"(aliases: {sorted(_OPT_DTYPE_ALIASES)})"
        )
    return name


def _is_adam_state(node: Any) -> bool:
    return isinstance(node, optax.ScaleByAdamState)


def _map_adam_moments(state: Any, mu_fn, nu_fn) -> Any:
    """Apply ``mu_fn``/``nu_fn`` to every moment LEAF of every
    ``ScaleByAdamState`` in an optimizer-state tree, leaving all other
    state (schedule counts, clip state, MultiSteps bookkeeping)
    untouched.  ``is_leaf``-based so it finds adam states at any
    nesting depth (chains, masked transforms, MultiSteps inner)."""

    def conv(node):
        if _is_adam_state(node):
            return optax.ScaleByAdamState(
                count=node.count,
                mu=jax.tree_util.tree_map(
                    mu_fn, node.mu, is_leaf=is_block_quantized
                ),
                nu=jax.tree_util.tree_map(
                    nu_fn, node.nu, is_leaf=is_block_quantized
                ),
            )
        return node

    return jax.tree_util.tree_map(conv, state, is_leaf=_is_adam_state)


def _compress_fns(dtype: str, block_size: int, min_quant_size: int):
    """(store, load) leaf converters for one moment kind."""

    def store_bf16(v):
        if is_block_quantized(v):
            return v
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
            return v.astype(jnp.bfloat16)
        return v

    def load_bf16(v):
        if hasattr(v, "dtype") and v.dtype == jnp.bfloat16:
            return v.astype(jnp.float32)
        return v

    def make_store_int8(sqrt_domain: bool):
        def store(v):
            if is_block_quantized(v):
                return v
            if (hasattr(v, "dtype")
                    and jnp.issubdtype(v.dtype, jnp.floating)
                    and v.size >= min_quant_size):
                return quantize_moment(
                    v, block_size=block_size, sqrt_domain=sqrt_domain
                )
            return v

        return store

    def load_int8(v):
        if is_block_quantized(v):
            return dequantize_moment(v)
        return v

    if dtype == "bfloat16":
        return (store_bf16, store_bf16), (load_bf16, load_bf16)
    return (
        (make_store_int8(False), make_store_int8(True)),
        (load_int8, load_int8),
    )


def quantize_opt_state(
    inner: "optax.GradientTransformation",
    dtype: str,
    block_size: int = DEFAULT_BLOCK_SIZE,
    min_quant_size: int = MIN_QUANT_SIZE,
) -> "optax.GradientTransformation":
    """Wrap ``inner`` so its adam moments persist in ``dtype``.

    ``dtype="int8"`` stores both moments block-scaled
    (:mod:`ops.optim_quant` — first moment linear, second moment sqrt
    domain; leaves under ``min_quant_size`` stay float).
    ``dtype="bfloat16"`` casts both moments to bf16.  Either way the
    inner update runs on a transient f32 view — inside a jitted donated
    step the conversion fuses into the update program, so only the
    compressed state occupies HBM between steps.
    """
    dtype = resolve_opt_state_dtype(dtype)
    if dtype in (None, "float32"):
        return inner
    (store_mu, store_nu), (load_mu, load_nu) = _compress_fns(
        dtype, block_size, min_quant_size
    )

    def compress(state):
        return _map_adam_moments(state, store_mu, store_nu)

    def decompress(state):
        return _map_adam_moments(state, load_mu, load_nu)

    def init(params):
        return compress(inner.init(params))

    def update(updates, state, params=None):
        new_updates, new_state = inner.update(
            updates, decompress(state), params
        )
        return new_updates, compress(new_state)

    return optax.GradientTransformation(init, update)


def apply_opt_state_dtype(adamw_tx, opt_state_dtype: Optional[str],
                          block_size: int = DEFAULT_BLOCK_SIZE):
    """The one-liner both model families call: wrap their adamw in the
    configured state-precision policy (``None``/``"float32"`` =
    unchanged)."""
    dtype = resolve_opt_state_dtype(opt_state_dtype)
    if dtype in (None, "float32"):
        return adamw_tx
    return quantize_opt_state(adamw_tx, dtype, block_size=block_size)


def opt_state_bytes(
    params: Any,
    dtype: Optional[str],
    block_size: int = DEFAULT_BLOCK_SIZE,
    min_quant_size: int = MIN_QUANT_SIZE,
) -> int:
    """Analytic HBM bytes of the PERSISTENT AdamW moment state under a
    precision policy.
    Counts both moments per parameter leaf; scalars/counts are noise
    and ignored.  ``dtype=None`` models the GPT legacy default (bf16
    first moment via ``mu_dtype``, f32 second)."""
    dtype = resolve_opt_state_dtype(dtype) if dtype is not None else None
    total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        size = int(getattr(leaf, "size", 0) or 0)
        if size == 0:
            continue
        if dtype == "int8" and size >= min_quant_size:
            padded = size + ((-size) % block_size)
            per_moment = padded + 4 * (padded // block_size)
            total += 2 * per_moment
        elif dtype == "bfloat16":
            total += 2 * 2 * size
        elif dtype is None:
            total += (2 + 4) * size  # bf16 mu + f32 nu
        else:  # float32 policy, or int8 policy's small-leaf carve-out
            total += 2 * 4 * size
    return total
