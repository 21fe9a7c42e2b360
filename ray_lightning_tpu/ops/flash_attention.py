"""Pallas TPU flash-attention (causal): forward + fused backward kernels.

The hot op of the transformer family, written TPU-first per the Pallas
playbook (``/opt/skills/guides/pallas_guide.md``):

* grid ``(batch*heads, seq/block_q)`` — one program per query block;
* K/V live in VMEM per (batch,head) and are walked in ``block_k`` slices
  with online softmax (running max/denominator in float32 scratch carries)
  — memory is O(seq · head_dim) instead of the O(seq²) logits tensor;
* the causal structure bounds the inner loop: query block ``i`` visits only
  key blocks ``<= i`` (the upper half of the score matrix is never
  computed, ~2× fewer MXU ops than mask-and-discard);
* logits/accumulators in float32, inputs/outputs in the caller's dtype
  (bfloat16 in the mixed-precision recipe).

Backward pass (FlashAttention-2 style, two kernels):

* the forward additionally emits the per-row log-sum-exp ``lse = m +
  log l``, broadcast across a 128-lane minor dim (the TPU-native layout
  for per-row scalars — same trick as jax.experimental.pallas.ops.tpu);
* ``delta = rowsum(dO · O)`` is computed in-kernel from the O block (a
  few VPU ops on resident data — no O(S·lane) HBM round-trip);
* **dq kernel**: one program per query block, walks key blocks ``<= i``,
  recomputes ``p = exp(s − lse)`` and accumulates ``ds @ K``;
* **dk/dv kernel**: one program per key block, walks query blocks
  ``>= floor(k/block_q)``, accumulating ``pᵀ @ dO`` and ``dsᵀ @ Q``.

So the O(S²) logits tensor is never materialized in either direction —
memory stays O(S·D) at any context length, which is what makes long-
context (ring/sequence-parallel) training viable.

(The reference framework has no analogue — its compute is opaque torch
modules; this file exists because the TPU build owns its model math.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"]

DEFAULT_BLOCK_Q = 512  # tuned on v5e: 512² beats 256² by ~30% fwd+bwd
DEFAULT_BLOCK_K = 512


def pick_block(seq_len: int, prefer: int = DEFAULT_BLOCK_Q) -> Optional[int]:
    """Largest lane-aligned block (<= prefer) that divides ``seq_len``.

    Keeps short/odd sequence lengths (768, 1280, ...) on the flash path
    instead of silently falling back when they don't divide the tuned
    default.  Returns None when no 128-multiple block fits."""
    block = min(prefer, seq_len)
    while block >= 128:
        if seq_len % block == 0 and block % 128 == 0:
            return block
        block //= 2
    return None
_NEG_INF = -1e30
# Lane quantum for block_k (per-row stats are broadcast across lanes in
# VMEM, and the backward tiles them in block_k-wide sweeps).
_LANE = 128
# HBM width of the per-row lse stat.  In VMEM the tile is lane-padded
# anyway, but the HBM array is (BH, S, _STAT_W) — at 128 the saved-
# residual traffic was ~100 MB/layer of 128x-redundant f32 (the single
# largest line in the step profile); 8 keeps a legal f32 tile while
# cutting that 16x.
_STAT_W = 8


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, scale, block_q,
                block_k, head_dim):
    # MXU discipline: dot inputs stay in the CALLER's dtype (bf16 in the
    # mixed-precision recipe — f32 inputs would run the MXU at a fraction
    # of peak); accumulation is always f32 via preferred_element_type, and
    # the softmax statistics never leave f32.  ``scale`` is folded into
    # the f32 scores, not pre-multiplied into q (no bf16 rounding of q).
    q = q_ref[0]  # (block_q, d)
    qi = pl.program_id(1)
    q_base = qi * block_q

    def make_body(masked):
        def body(kb, carry):
            acc, m, l = carry
            k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (block_q, block_k) f32
            if masked:
                q_pos = q_base + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * corr + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return acc_new, m_new, l_new
        return body

    # Causal structure: key blocks entirely below the diagonal need no
    # mask (saves the iota/compare/where VPU passes on ~all blocks); only
    # blocks straddling the diagonal mask.  Last visible block index:
    # cdiv(q_base + block_q, block_k).
    num_full = q_base // block_k            # fully-visible blocks
    num_kb = pl.cdiv(q_base + block_q, block_k)
    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    carry = jax.lax.fori_loop(0, num_full, make_body(False), (acc0, m0, l0))
    acc, m, l = jax.lax.fori_loop(num_full, num_kb, make_body(True), carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (block_q, _STAT_W))


def _interpret() -> bool:
    from ray_lightning_tpu.ops.kernel_probe import _interpret as shared

    return shared()


def _flash_fwd_bhsd(q, k, v, scale, block_q, block_k, want_lse=True):
    """q/k/v: (BH, S, D) merged batch-heads layout -> (out, lse|None).

    ``want_lse=False`` (the primal, non-differentiated path — eval/
    predict) compiles a forward-only kernel with a single output, so no
    O(BH·S·lane) f32 lse tensor is allocated or written.
    """
    bh, s, d = q.shape
    dv = v.shape[-1]    # the values' own width (the accumulator's)
    grid = (bh, s // block_q)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        head_dim=dv,
    )
    out_shape = jax.ShapeDtypeStruct((bh, s, dv), q.dtype)
    out_spec = pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0))
    lse_spec = pl.BlockSpec((1, block_q, _STAT_W), lambda b, i: (b, i, 0))
    result = pl.pallas_call(
        kernel,
        out_shape=(
            out_shape,
            jax.ShapeDtypeStruct((bh, s, _STAT_W), jnp.float32),
        ) if want_lse else out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(out_spec, lse_spec) if want_lse else out_spec,
        interpret=_interpret(),
        name="rlt_flash_fwd",
    )(q, k, v)
    return result if want_lse else (result, None)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dk_ref,
                dv_ref, dqp_ref, *, scale, block_q, block_k, head_dim,
                seq_len):
    """One program per KEY block: dk/dv accumulate in registers across the
    query-block walk, and dq contributions are written as a per-key-block
    PARTIAL plane (summed by one cheap XLA reduction afterwards).

    Fusing dq into the dk/dv walk shares the s/p/dp/ds recomputation both
    would otherwise do independently — 5 MXU dots per block pair instead
    of 7 across two kernels.
    """
    ki = pl.program_id(1)
    k_base = ki * block_k
    k = k_ref[0]                                      # (block_k, d)
    v = v_ref[0]
    # Query blocks before the causal frontier contribute nothing — zero
    # exactly those rows (the walk below rewrites everything from the
    # frontier on; zeroing the whole plane would double-write ~half of it
    # on this bandwidth-sensitive path).
    zero_blk = jnp.zeros((block_q, head_dim), dqp_ref.dtype)

    def _zero_dead(qb, _):
        dqp_ref[0, 0, pl.ds(qb * block_q, block_q), :] = zero_blk
        return 0

    jax.lax.fori_loop(0, k_base // block_q, _zero_dead, 0)

    def make_body(masked):
        def body(qb, carry):
            dk_acc, dv_acc = carry
            q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
            do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
            lse = jnp.broadcast_to(
                lse_ref[0, pl.ds(qb * block_q, block_q), :1],
                (block_q, block_k),
            )
            o_blk = o_ref[0, pl.ds(qb * block_q, block_q), :]
            # delta = rowsum(dO · O) in-kernel: a few VPU ops on resident
            # data instead of an O(S·lane) f32 HBM round-trip per layer.
            delta = jnp.sum(
                do_blk.astype(jnp.float32) * o_blk.astype(jnp.float32),
                axis=1, keepdims=True,
            )
            di = jnp.broadcast_to(delta, (block_q, block_k))
            s = jax.lax.dot_general(
                q_blk, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                 # (block_q, block_k)
            if masked:
                q_pos = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0
                )
                k_pos = k_base + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1
                )
                s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
            p = jnp.exp(s - lse)
            dv_new = dv_acc + jax.lax.dot_general(
                p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                         # (block_k, d)
            dp = jax.lax.dot_general(
                do_blk, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # scale folded into ds: dk = (ds*scale)^T @ Q, dq = (ds*scale) @ K.
            ds = (p * (dp - di) * scale).astype(q_blk.dtype)
            dk_new = dk_acc + jax.lax.dot_general(
                ds, q_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dq_part = jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                         # (block_q, d)
            dqp_ref[0, 0, pl.ds(qb * block_q, block_q), :] = (
                dq_part.astype(dqp_ref.dtype)
            )
            return dk_new, dv_new
        return body

    # Causal bound from below: query blocks before this key block see
    # nothing here; blocks straddling the diagonal mask, later blocks see
    # the whole key block and skip the mask.
    qb_start = k_base // block_q
    qb_mask_end = pl.cdiv(k_base + block_k, block_q)
    zeros = jnp.zeros((block_k, head_dim), jnp.float32)
    carry = jax.lax.fori_loop(
        qb_start, qb_mask_end, make_body(True), (zeros, zeros)
    )
    dk, dv = jax.lax.fori_loop(
        qb_mask_end, seq_len // block_q, make_body(False), carry
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_bhsd(q, k, v, out, lse, g, scale, block_q, block_k):
    """Backward over (BH, S, D) tensors; returns (dq, dk, dv)."""
    bh, s, d = q.shape
    nkb = s // block_k
    dk, dv, dqp = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            head_dim=d, seq_len=s,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
            # dq partials per key block, in the input dtype: each partial
            # is one f32-accumulated dot rounded once (same rounding the
            # two-kernel design paid), and the few-term cross-block sum
            # below runs in f32 — while the partial plane's HBM round-trip
            # is half the width.
            jax.ShapeDtypeStruct((bh, nkb, s, d), q.dtype),
        ),
        grid=(bh, nkb),
        in_specs=[
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, _STAT_W), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, s, d), lambda b, i: (b, i, 0, 0)),
        ),
        interpret=_interpret(),
        name="rlt_flash_bwd",
    )(q, k, v, g, lse, out)
    dq = jnp.sum(dqp.astype(jnp.float32), axis=1).astype(q.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash(scale, block_q, block_k, q, k, v):
    b, s, h, _ = q.shape

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    out, _ = _flash_fwd_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), scale, block_q, block_k,
        want_lse=False,
    )
    return out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)


def _flash_vjp_fwd(scale, block_q, block_k, q, k, v):
    b, s, h, d = q.shape
    if v.shape[-1] != d:
        raise NotImplementedError(
            f"flash attention's backward kernels take one head width; "
            f"values of {v.shape[-1]} beside queries of {d} run forward "
            f"only (serving prefill)")

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    # Named so a rematerialized block can SAVE these residuals (policy
    # save_only_these_names / save_from_both_policies) instead of
    # re-running the forward kernel (out/lse) or re-transposing the
    # inputs (q/k/v in kernel layout) to regenerate them.
    from jax.ad_checkpoint import checkpoint_name

    qm = checkpoint_name(to_bhsd(q), "flash_q")
    km = checkpoint_name(to_bhsd(k), "flash_k")
    vm = checkpoint_name(to_bhsd(v), "flash_v")
    out, lse = _flash_fwd_bhsd(qm, km, vm, scale, block_q, block_k)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (
        out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
        (qm, km, vm, out, lse, (b, s, h, d)),
    )


def _flash_vjp_bwd(scale, block_q, block_k, residuals, g):
    qm, km, vm, out, lse, (b, s, h, d) = residuals
    gm = g.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    dq, dk, dv = _flash_bwd_bhsd(
        qm, km, vm, out, lse, gm, scale, block_q, block_k
    )

    def from_bhsd(x):
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return from_bhsd(dq), from_bhsd(dk), from_bhsd(dv)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Causal flash attention, (B, S, H, D) -> (B, S, H, D).  The
    forward takes values of another width than the queries' and keys'
    (``v (B, S, H, Dv)`` -> ``(B, S, H, Dv)``: latent attention's 128
    beside 192); the backward does not."""
    _, s, _, d = q.shape
    scale = (d ** -0.5) if scale is None else scale
    if block_q is None:
        block_q = pick_block(s) or min(DEFAULT_BLOCK_Q, s)
    if block_k is None:
        block_k = pick_block(s, DEFAULT_BLOCK_K) or min(DEFAULT_BLOCK_K, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq_len {s} must be divisible by block_q={block_q} and "
            f"block_k={block_k}"
        )
    if block_k % _LANE:
        raise ValueError(
            f"block_k={block_k} must be a multiple of {_LANE} (lane "
            f"quantum of the blocked score sweeps)"
        )
    return _flash(scale, block_q, block_k, q, k, v)
