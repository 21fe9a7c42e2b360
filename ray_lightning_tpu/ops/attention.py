"""Causal multi-head attention: XLA reference + implementation dispatcher.

All implementations share one contract::

    causal_attention(q, k, v) -> out      # shapes (batch, seq, heads, dim)

* ``impl="xla"`` — einsum + masked softmax; XLA fuses this well and it runs
  anywhere (CPU test meshes included).  This is also the numerical
  reference the Pallas/ring implementations are tested against.
* ``impl="flash"`` — the Pallas TPU kernel (:mod:`.flash_attention`):
  blocked online-softmax, O(seq) memory, causal blocks skipped.
* ``impl="auto"`` — flash on TPU when shapes and the mesh allow (bare on
  one device, inside a ``shard_map`` island on a batch-only mesh), else
  XLA.

Ring (sequence-parallel) attention has a different calling convention — it
runs *inside* ``shard_map`` over a sequence-sharded axis — and lives in
:mod:`.ring_attention`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["causal_attention", "xla_causal_attention"]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax
# rows finite (causal rows always have >=1 unmasked entry, but -inf
# produces nan gradients through where()).


def xla_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
) -> jax.Array:
    """Reference causal attention, (B, S, H, D) -> (B, S, H, D).

    Softmax is computed in float32 regardless of input dtype (bfloat16
    activations keep full-precision normalizers — the standard TPU mixed-
    precision recipe), output is cast back to the input dtype.
    """
    b, s, h, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    logits = jnp.where(q_pos >= k_pos, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


_BATCH_AXES = ("data", "fsdp")


def _multi_device(mesh) -> bool:
    return mesh is not None and getattr(mesh, "size", 1) > 1


def _island_axes(mesh, batch: int) -> Optional[tuple]:
    """The axes a flash island shards the batch over, or ``None`` where
    attention is not batch-local on ``mesh``: some axis wider than one
    device shards something else, or the batch does not divide."""
    if any(mesh.shape[a] > 1 and a not in _BATCH_AXES
           for a in mesh.axis_names):
        return None
    if batch % mesh.size:
        return None
    return tuple(a for a in mesh.axis_names if a in _BATCH_AXES)


def _flash_supported(q: jax.Array, mesh=None, manual: bool = False) -> bool:
    """Whether ``impl="auto"`` takes the flash kernel: a function of the
    backend, the shapes, the mesh and ``RLT_DISABLE_KERNELS`` — nothing
    is compiled to find out.

    ``mesh`` is the mesh the enclosing jit partitions over (``None`` =
    one device).  A Mosaic kernel is opaque to the GSPMD partitioner, so
    on a multi-device mesh the kernel runs per device inside a
    ``shard_map`` island — possible only where attention is batch-local:
    every mesh axis shards the batch (``data``/``fsdp``) and the batch
    divides over them.  Meshes that shard heads or sequence take the XLA
    path.  ``manual=True`` says the caller's body is already per-device
    (inside a ``shard_map``), where the kernel runs bare.
    """
    from ray_lightning_tpu.ops.kernel_probe import kernel_family_disabled

    if kernel_family_disabled("flash"):
        return False
    if jax.default_backend() != "tpu":
        return False
    b, s, _, d = q.shape
    if (_multi_device(mesh) and not manual
            and _island_axes(mesh, b) is None):
        return False
    from ray_lightning_tpu.ops import flash_attention as fa

    # Kernel constraints: some 128-multiple block must divide seq (per-row
    # softmax stats are stored broadcast across a 128-lane minor dim, and
    # the backward kernels tile them in block_k/128 repeats).
    return fa.pick_block(s) is not None and d in (64, 128, 256)


def _flash_island(q, k, v, scale, mesh):
    """The flash kernel per device on a batch-only mesh (jit →
    shard_map → pallas, as ``fused_lm_head_cross_entropy_sharded``).
    Attention is batch-local: no collective in either direction."""
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.ops.flash_attention import flash_attention

    axes = _island_axes(mesh, q.shape[0])
    if axes is None:
        raise ValueError(
            f"impl='flash' on mesh axes {mesh.axis_names} "
            f"(shape {dict(mesh.shape)}) with batch {q.shape[0]}: the "
            "kernel runs per device only where every axis shards the "
            "batch evenly; use impl='xla' (or 'auto')"
        )
    spec = P(axes)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
    impl: str = "auto",
    mesh=None,
    manual: bool = False,
) -> jax.Array:
    """Dispatching causal attention (see module docstring).  ``mesh`` /
    ``manual``: see :func:`_flash_supported`."""
    if impl == "auto":
        impl = "flash" if _flash_supported(q, mesh, manual) else "xla"
    if impl == "xla":
        return xla_causal_attention(q, k, v, scale)
    if impl == "flash":
        if _multi_device(mesh) and not manual:
            return _flash_island(q, k, v, scale, mesh)
        from ray_lightning_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, scale)
    raise ValueError(f"Unknown attention impl {impl!r} (auto|xla|flash)")
