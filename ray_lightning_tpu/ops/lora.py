"""Batched-gather LoRA application (BGMV): ``y += (x @ A[ids]) @ B[ids]``.

The device-side primitive of multi-tenant LoRA serving
(``serve/lora.py``): every hook site holds the pool's adapters STACKED
in one resident buffer — ``A (N, d, r)`` / ``B (N, r, k)`` per layer —
and a dispatch applies each row's own adapter by gathering its factors
with an int32 ``ids`` operand (the S-LoRA/Punica shape).  ``ids`` is a
VALUE, never a shape, so a batch can mix any adapters and the serving
plane's compiled-once program set never grows with the tenant count.

Slot 0 is the pool's NULL adapter (zero factors): rows with no adapter
gather zeros and pay one rank-``r`` matmul pair for a delta of exactly
0.0 — no branch in the program, mixed base/adapter batches ride the
same dispatch.

Two implementations, selected ONCE at engine build (never per call):

* ``xla`` — gathered einsum pair.  Works everywhere; on CPU (the test
  container) it is the only sensible path.
* ``pallas`` — a per-row kernel that scalar-prefetches ``ids`` and DMAs
  ONLY the selected adapter's factors into VMEM (the gathered einsum
  materializes an ``(W, d, r)`` copy first).  Selected on TPU
  (:func:`resolve_bgmv_impl`); ``RLT_LORA_BGMV=xla|pallas`` forces an
  arm for A/B runs.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["lora_delta", "apply_lora", "bgmv_xla", "bgmv_pallas",
           "resolve_bgmv_impl"]


def apply_lora(y: jax.Array, h: jax.Array, ad, site: str,
               ids, impl: str) -> jax.Array:
    """``y`` plus hook-site ``site``'s per-slot adapter delta — the ONE
    application hook every program family (static trunk, paged decode,
    paged verify) traces, so the contract (factor naming, id
    semantics, a future per-site operand) has a single edit point.
    ``ad is None`` (every non-serving caller) returns ``y`` unchanged:
    the traced graph is byte-identical to pre-LoRA rounds."""
    if ad is None:
        return y
    return y + lora_delta(h, ad[f"{site}_a"], ad[f"{site}_b"], ids,
                          impl=impl)


def bgmv_xla(h: jax.Array, a: jax.Array, b: jax.Array,
             ids: jax.Array) -> jax.Array:
    """Gathered two-matmul delta for ``h (W, d)``: ``(h @ a[ids]) @
    b[ids]`` → ``(W, k)``.  ``b`` carries the adapter's LoRA scale
    pre-folded (``AdapterPool.add``), so there is no per-row scale
    operand."""
    t = jnp.einsum("wd,wdr->wr", h, a[ids].astype(h.dtype))
    return jnp.einsum("wr,wrk->wk", t, b[ids].astype(h.dtype))


def bgmv_pallas(h: jax.Array, a: jax.Array, b: jax.Array,
                ids: jax.Array) -> jax.Array:
    """Per-row BGMV kernel: grid over the W rows; each step
    scalar-prefetches ``ids[w]`` and block-indexes the stacked factor
    buffers with it, so only the SELECTED adapter's ``(d, r)``/``(r,
    k)`` factors cross HBM→VMEM — the whole point over the gather."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_lightning_tpu.ops.kernel_probe import _interpret

    W, d = h.shape
    k = b.shape[-1]

    def kernel(ids_ref, h_ref, a_ref, b_ref, out_ref):
        del ids_ref  # consumed by the index maps
        t = jnp.dot(h_ref[0], a_ref[0],
                    preferred_element_type=jnp.float32)
        out_ref[0] = jnp.dot(
            t, b_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)

    # Rows ride a unit middle axis — ``(W, 1, d)`` in ``(1, 1, d)``
    # blocks: a TPU block's last two dims must be (8, 128)-aligned or
    # span the array's, and a one-row ``(1, d)`` block of ``(W, d)`` is
    # neither once W > 1.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(W,),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda w, ids: (w, 0, 0)),
            pl.BlockSpec((1, a.shape[1], a.shape[2]),
                         lambda w, ids: (ids[w], 0, 0)),
            pl.BlockSpec((1, b.shape[1], b.shape[2]),
                         lambda w, ids: (ids[w], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, k), lambda w, ids: (w, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, 1, k), h.dtype),
        interpret=_interpret(),
        name="rlt_lora_bgmv",
    )(ids.astype(jnp.int32), h.reshape(W, 1, d), a.astype(h.dtype),
      b.astype(h.dtype)).reshape(W, k)


def resolve_bgmv_impl() -> str:
    """Pick the BGMV arm once (engine build time, never per dispatch).

    ``RLT_LORA_BGMV`` forces an arm; otherwise the Pallas kernel on TPU
    and the gathered einsum elsewhere (off-TPU the gather is simply the
    faster path).  Nothing is compiled to decide: the kernel's blocks
    span each operand's last two dims, so the chip's compiler takes any
    ``(d, r, k)`` and dtype (``tests/test_chip_compile.py`` holds a case), and a
    shape it did refuse would be an error, not a silent change of arm.
    """
    forced = os.environ.get("RLT_LORA_BGMV", "").strip().lower()
    if forced in ("xla", "pallas"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def lora_delta(h: jax.Array, a: jax.Array, b: jax.Array,
               ids: jax.Array, impl: str = "xla") -> jax.Array:
    """Adapter delta for ``h`` of shape ``(W, d)`` or ``(B, T, d)``.

    ``ids`` matches the leading axis (one adapter per row/sequence).
    The 3-D form (prefill buckets, verify windows) flattens to rows
    with per-position repeated ids, so both arms serve every program
    family from one entry point.
    """
    if h.ndim == 3:
        B, T, d = h.shape
        flat = lora_delta(
            h.reshape(B * T, d), a, b, jnp.repeat(ids, T), impl=impl
        )
        return flat.reshape(B, T, -1)
    if impl == "pallas":
        return bgmv_pallas(h, a, b, ids)
    return bgmv_xla(h, a, b, ids)
