"""Fused LM-head + cross-entropy, vocab-chunked — no (B, T, V) tensor.

The naive LM loss (``logits = x @ wte.T`` then softmax-CE) materializes a
``(B, T, V)`` float32 logits tensor in HBM — for GPT-2-small at B=16,
T=1024, V=50304 that is a ~3.3 GB intermediate written and re-read every
step (and its ``(B, T, V)`` gradient again in the backward), which alone
costs ~17% of the step on a v5e.  The reference framework never faces
this because its models are external torch modules
(``/root/reference/examples/ray_ddp_sharded_example.py:48-71``); a
TPU-native framework that owns its flagship LM must own the fix.

Design (TPU/XLA-first):

* **Vocab chunking with online logsumexp.**  ``lax.scan`` over chunks of
  the vocabulary: each iteration computes ``(B, T, Vc)`` logits on the
  fly (bf16 MXU matmul, f32 accumulation), folds them into running
  ``(max, sumexp)`` statistics and the gathered gold-label logit, then
  discards them.  Peak live logits memory drops from ``N*V`` to
  ``N*Vc``.
* **Why chunk vocab, not tokens:** under GSPMD the batch/seq dims are
  sharded over the ``data``(+``fsdp``/``sp``) mesh axes and ``wte`` is
  feature-sharded ``P(None, "tensor")`` (see
  ``models/gpt.py:param_partition_specs``).  Scanning over *vocab* rows
  slices only the replicated dim — no resharding, no cross-device
  gathers; the contraction over the tensor-sharded ``d`` stays a local
  matmul + psum exactly as in the unchunked head.
* **Custom VJP with chunk recompute.**  Residuals are just
  ``(x, wte, targets, lse)`` — the backward rebuilds each chunk's
  logits, forms ``dlogits = (softmax - onehot) * g`` chunk-locally, and
  accumulates ``dx`` (f32 carry) and the per-chunk ``dwte`` rows.  The
  ``(B, T, V)`` gradient tensor never exists either.

Numerics: matmuls run in ``compute_dtype`` (bf16 on TPU) with float32
``preferred_element_type`` accumulation; softmax statistics, the loss and
both gradients accumulate in float32.  With ``compute_dtype=float32``
the result matches the naive path to ~1e-6 (tested in
``tests/test_ops.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.ops.kernel_probe import (
    _interpret,
    kernel_family_disabled,
)

__all__ = [
    "fused_lm_head_cross_entropy",
    "fused_lm_head_cross_entropy_sharded",
    "naive_lm_head_cross_entropy",
]

_NEG_INF = -1e30  # finite stand-in for -inf: keeps exp/max well-defined


def _pick_num_chunks(vocab_size: int, target_chunk: int = 8192) -> int:
    return max(1, -(-vocab_size // target_chunk))  # ceil div


def _chunk_wte(wte: jax.Array, num_chunks: int) -> Tuple[jax.Array, int]:
    """(V, d) -> (K, Vc, d), zero-padding V up to K*Vc.

    Vc is rounded up to a multiple of 128 so every chunk matmul and the
    (..., Vc) softmax/onehot ops tile cleanly on the 8x128 vector lanes
    (the valid-mask already neutralizes the padded rows)."""
    V, d = wte.shape
    Vc = -(-V // num_chunks)
    Vc = -(-Vc // 128) * 128
    pad = num_chunks * Vc - V
    if pad:
        wte = jnp.concatenate(
            [wte, jnp.zeros((pad, d), wte.dtype)], axis=0
        )
    return wte.reshape(num_chunks, Vc, d), Vc


def _chunk_logits(x, wte_chunk, offset, vocab_size, compute_dtype):
    """x (..., d) @ wte_chunk (Vc, d)^T -> (..., Vc) f32, padded rows
    masked to -inf."""
    Vc = wte_chunk.shape[0]
    logits = jnp.einsum(
        "...d,vd->...v",
        x.astype(compute_dtype),
        wte_chunk.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    # Mask vocab ids >= vocab_size (zero-padded rows of the last chunk).
    valid = (offset + jnp.arange(Vc)) < vocab_size
    return jnp.where(valid, logits, _NEG_INF)


# Tile sizes chosen for the ~16 MB/core VMEM budget with double-buffered
# input blocks: at the d=1536 cap the worst kernel (dw, vocab-major
# accumulator) holds ~10 MB.  Token counts that don't divide _CE_BLOCK_T
# are zero-padded (a padded row's cotangent is zero, so it contributes
# nothing backward); larger models fall back to the GSPMD-safe scan.
_CE_BLOCK_T = 512
_CE_BLOCK_V = 512
_CE_MAX_D = 1536
_LANE = 128


def _ce_fwd_kernel(x_ref, w_ref, t_ref, loss_ref, lse_ref, m_sc, s_sc, g_sc,
                   *, vocab_size, block_v, num_vb, vma=()):
    """Forward CE tile: one (token-block × vocab-block) step.

    Grid is (token blocks, vocab blocks) with vocab innermost: the online
    softmax statistics (running max / sumexp / gold logit) live in VMEM
    scratch across the vocab sweep, so the (Tb, Vb) logits tile never
    leaves VMEM — zero HBM logits traffic (the scan fallback writes and
    re-reads every chunk).
    """
    from jax.experimental import pallas as pl

    vi = pl.program_id(1)

    def _c(val):  # promote kernel constants under the interpreter
        return _to_varying(val, vma)

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = _c(jnp.full(m_sc.shape, _NEG_INF, jnp.float32))
        s_sc[...] = _c(jnp.zeros(s_sc.shape, jnp.float32))
        g_sc[...] = _c(jnp.zeros(g_sc.shape, jnp.float32))

    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # (Tb, Vb) f32
    tb, vb = logits.shape
    vpos = _c(vi * block_v
              + jax.lax.broadcasted_iota(jnp.int32, (tb, vb), 1))
    logits = jnp.where(
        vpos < _c(jnp.int32(vocab_size)), logits,
        _c(jnp.float32(_NEG_INF))
    )
    m_old = m_sc[:, :1]
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    s_new = s_sc[:, :1] * corr + jnp.sum(
        jnp.exp(logits - m_new), axis=1, keepdims=True
    )
    # Gold logit: exactly one (or zero) hit per row in this vocab block.
    hit = vpos == t_ref[:, :1]
    g_new = g_sc[:, :1] + jnp.sum(
        jnp.where(hit, logits, _c(jnp.float32(0.0))), axis=1,
        keepdims=True
    )
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    s_sc[...] = jnp.broadcast_to(s_new, s_sc.shape)
    g_sc[...] = jnp.broadcast_to(g_new, g_sc.shape)

    @pl.when(vi == num_vb - 1)
    def _emit():
        lse = m_new + jnp.log(s_new)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        loss_ref[...] = jnp.broadcast_to(lse - g_new, loss_ref.shape)


def _flatten_pad(x, targets, compute_dtype, extras=()):
    """Flatten (..., d) tokens and zero-pad to a _CE_BLOCK_T multiple.

    Padded rows produce garbage forward values (their target of 0 DOES
    match vocab position 0) — inertness comes from the caller slicing
    outputs back to ``n`` rows, and, in the backward, from the cotangent
    ``g`` being zero-padded here so padded rows contribute nothing to
    dx/dwte.  Returns (x2, t2, n_valid, n_pad, padded_extras).
    """
    d = x.shape[-1]
    x2 = x.reshape(-1, d).astype(compute_dtype)
    t1 = targets.reshape(-1)
    n = x2.shape[0]
    n_pad = -(-n // _CE_BLOCK_T) * _CE_BLOCK_T
    if n_pad != n:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((n_pad - n, d), x2.dtype)], axis=0
        )
        t1 = jnp.concatenate(
            [t1, jnp.zeros((n_pad - n,), t1.dtype)], axis=0
        )
    t2 = jnp.broadcast_to(t1[:, None], (n_pad, _LANE))
    out = []
    for extra in extras:
        e1 = extra.reshape(-1).astype(jnp.float32)
        if n_pad != n:
            e1 = jnp.concatenate([e1, jnp.zeros((n_pad - n,), e1.dtype)])
        out.append(jnp.broadcast_to(e1[:, None], (n_pad, _LANE)))
    return x2, t2, n, n_pad, tuple(out)


def _pad_vocab(wte, compute_dtype):
    V, d = wte.shape
    vpad = -(-V // _CE_BLOCK_V) * _CE_BLOCK_V
    wp = wte.astype(compute_dtype)
    if vpad != V:
        wp = jnp.concatenate(
            [wp, jnp.zeros((vpad - V, d), wp.dtype)], axis=0
        )
    return wp, vpad


def _vma_of(val) -> frozenset:
    """Manual mesh axes ``val`` varies over (empty outside shard_map)."""
    return frozenset(jax.typeof(val).vma)


def _to_varying(val, vma):
    """Promote ``val`` to vary over every axis of ``vma`` it does not
    already vary over."""
    missing = tuple(frozenset(vma) - _vma_of(val))
    return jax.lax.pcast(val, missing, to="varying") if missing else val


def _out_struct(shape, dtype, vma):
    """ShapeDtypeStruct carrying the varying-manual-axes type when inside
    a shard_map region (pallas_call requires explicit out vma there)."""
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _ce_fwd_pallas(x, wte, targets, compute_dtype):
    """Kernel-path forward over flattened tokens.  Returns (loss, lse),
    both f32 with ``targets``'s shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = targets.shape
    d = x.shape[-1]
    V = wte.shape[0]
    bt = _CE_BLOCK_T
    bv = _CE_BLOCK_V
    x2, t2, n, n_pad, _ = _flatten_pad(x, targets, compute_dtype)
    wp, vpad = _pad_vocab(wte, compute_dtype)
    # Inside shard_map every pallas operand/output must carry one
    # consistent vma type: promote the (replicated) head to the token
    # operands' axes; outputs vary the same way.
    vma = _vma_of(x2) | _vma_of(t2) | _vma_of(wp)
    if vma:
        x2, t2, wp = (_to_varying(v, vma) for v in (x2, t2, wp))
    num_vb = vpad // bv
    interp = _interpret()
    kernel = partial(
        _ce_fwd_kernel, vocab_size=V, block_v=bv, num_vb=num_vb,
        vma=tuple(sorted(vma)) if interp else (),
    )
    loss, lse = pl.pallas_call(
        kernel,
        out_shape=(
            _out_struct((n_pad, _LANE), jnp.float32, vma),
            _out_struct((n_pad, _LANE), jnp.float32, vma),
        ),
        grid=(n_pad // bt, num_vb),
        in_specs=[
            pl.BlockSpec((bt, d), lambda t, v: (t, 0)),
            pl.BlockSpec((bv, d), lambda t, v: (v, 0)),
            pl.BlockSpec((bt, _LANE), lambda t, v: (t, 0)),
        ],
        out_specs=(
            pl.BlockSpec((bt, _LANE), lambda t, v: (t, 0)),
            pl.BlockSpec((bt, _LANE), lambda t, v: (t, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bt, _LANE), jnp.float32),
            pltpu.VMEM((bt, _LANE), jnp.float32),
            pltpu.VMEM((bt, _LANE), jnp.float32),
        ],
        interpret=interp,
        name="rlt_ce_fwd",
    )(x2, wp, t2)
    return loss[:n, 0].reshape(shape), lse[:n, 0].reshape(shape)


def _pallas_fwd_ok(d: int, compute_dtype) -> bool:
    """The kernel path needs a lane-aligned, VMEM-sized feature dim and
    the ``ce`` family not switched off (``RLT_DISABLE_KERNELS``); other
    shapes use the scan path (ragged token counts are fine — they are
    zero-padded).  The d cap is in compute-dtype BYTES: the VMEM budget
    was sized for bf16 tiles, so f32 compute halves the allowed feature
    dim rather than overflowing VMEM at lowering time.  This is the
    whole gate: a kernel the compiler refuses is an error."""
    max_d = _CE_MAX_D * 2 // jnp.dtype(compute_dtype).itemsize
    return (d % 128 == 0 and d <= max_d
            and not kernel_family_disabled("ce"))


def _ce_logits_tile(x_ref, w_ref, vi, block_v, vocab_size, vma=()):
    """Shared tile recompute: (Tb, d) x (Vb, d)^T -> masked f32 logits.

    ``vma`` is non-empty only under the Pallas INTERPRETER inside a
    shard_map region, where the kernel body is evaluated as jax ops and
    fresh constants (iota) must be promoted to the refs' varying type.
    Compiled Mosaic never sees it."""
    def _c(val):
        return _to_varying(val, vma)

    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    tb, vb = logits.shape
    vpos = _c(vi * block_v
              + jax.lax.broadcasted_iota(jnp.int32, (tb, vb), 1))
    valid = vpos < _c(jnp.int32(vocab_size))
    return jnp.where(valid, logits, _c(jnp.float32(_NEG_INF))), vpos


def _ce_dlogits(logits, vpos, t_ref, lse_ref, g_ref):
    p = jnp.exp(logits - lse_ref[:, :1])
    onehot = (vpos == t_ref[:, :1]).astype(jnp.float32)
    return (p - onehot) * g_ref[:, :1]


def _ce_bwd_dx_kernel(x_ref, w_ref, t_ref, lse_ref, g_ref, dx_ref, acc_sc,
                      *, vocab_size, block_v, num_vb, vma=()):
    """dx tile: token-major grid, vocab innermost; dx accumulates in VMEM
    across the vocab sweep.  The (Tb, Vb) dlogits tile never reaches HBM
    (the scan backward round-trips every chunk's logits AND dlogits)."""
    from jax.experimental import pallas as pl

    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_sc[...] = _to_varying(
            jnp.zeros(acc_sc.shape, jnp.float32), vma
        )

    logits, vpos = _ce_logits_tile(
        x_ref, w_ref, vi, block_v, vocab_size, vma
    )
    dlog = _ce_dlogits(logits, vpos, t_ref, lse_ref, g_ref)
    acc_sc[...] += jax.lax.dot_general(
        dlog.astype(x_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(vi == num_vb - 1)
    def _emit():
        dx_ref[...] = acc_sc[...]


def _ce_bwd_dw_kernel(x_ref, w_ref, t_ref, lse_ref, g_ref, dw_ref, acc_sc,
                      *, vocab_size, block_v, num_tb, vma=()):
    """dwte tile: vocab-major grid, tokens innermost; the (Vb, d) row
    gradient accumulates in VMEM across the token sweep."""
    from jax.experimental import pallas as pl

    vi = pl.program_id(0)
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_sc[...] = _to_varying(
            jnp.zeros(acc_sc.shape, jnp.float32), vma
        )

    logits, vpos = _ce_logits_tile(
        x_ref, w_ref, vi, block_v, vocab_size, vma
    )
    dlog = _ce_dlogits(logits, vpos, t_ref, lse_ref, g_ref)
    acc_sc[...] += jax.lax.dot_general(
        dlog.astype(x_ref.dtype), x_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ti == num_tb - 1)
    def _emit():
        dw_ref[...] = acc_sc[...]


def _ce_bwd_pallas(x, wte, targets, lse, g, compute_dtype):
    """Kernel-path backward: (dx, dwte) with zero HBM logits traffic.

    Two passes re-deriving the dlogits tile in VMEM: token-major for dx
    (contract over vocab), vocab-major for dwte (contract over tokens).
    One extra logits matmul vs the scan backward — MXU FLOPs traded for
    the HBM round-trips of every (N, Vc) chunk intermediate, the right
    side of the bargain on a bandwidth-bound step.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d = x.shape[-1]
    V = wte.shape[0]
    bt = _CE_BLOCK_T
    bv = _CE_BLOCK_V
    x2, t2, n, n_pad, (g2, lse2) = _flatten_pad(
        x, targets, compute_dtype, extras=(g, lse)
    )
    wp, vpad = _pad_vocab(wte, compute_dtype)
    vma = (_vma_of(x2) | _vma_of(t2) | _vma_of(wp) | _vma_of(g2)
           | _vma_of(lse2))
    if vma:
        x2, t2, wp, g2, lse2 = (
            _to_varying(v, vma) for v in (x2, t2, wp, g2, lse2)
        )
    num_vb = vpad // bv
    num_tb = n_pad // bt
    interp = _interpret()
    kvma = tuple(sorted(vma)) if interp else ()

    dx = pl.pallas_call(
        partial(_ce_bwd_dx_kernel, vocab_size=V, block_v=bv, num_vb=num_vb,
                vma=kvma),
        out_shape=_out_struct((n_pad, d), jnp.float32, vma),
        grid=(num_tb, num_vb),
        in_specs=[
            pl.BlockSpec((bt, d), lambda t, v: (t, 0)),
            pl.BlockSpec((bv, d), lambda t, v: (v, 0)),
            pl.BlockSpec((bt, _LANE), lambda t, v: (t, 0)),
            pl.BlockSpec((bt, _LANE), lambda t, v: (t, 0)),
            pl.BlockSpec((bt, _LANE), lambda t, v: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda t, v: (t, 0)),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interp,
        name="rlt_ce_bwd_dx",
    )(x2, wp, t2, lse2, g2)

    dw = pl.pallas_call(
        partial(_ce_bwd_dw_kernel, vocab_size=V, block_v=bv, num_tb=num_tb,
                vma=kvma),
        out_shape=_out_struct((vpad, d), jnp.float32, vma),
        grid=(num_vb, num_tb),
        in_specs=[
            pl.BlockSpec((bt, d), lambda v, t: (t, 0)),
            pl.BlockSpec((bv, d), lambda v, t: (v, 0)),
            pl.BlockSpec((bt, _LANE), lambda v, t: (t, 0)),
            pl.BlockSpec((bt, _LANE), lambda v, t: (t, 0)),
            pl.BlockSpec((bt, _LANE), lambda v, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bv, d), lambda v, t: (v, 0)),
        scratch_shapes=[pltpu.VMEM((bv, d), jnp.float32)],
        interpret=interp,
        name="rlt_ce_bwd_dw",
    )(x2, wp, t2, lse2, g2)

    dx = dx[:n].reshape(x.shape)
    return dx, dw[:V]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ce(x, wte, targets, num_chunks, compute_dtype, use_pallas):
    loss, _ = _fused_ce_vjp_fwd(
        x, wte, targets, num_chunks, compute_dtype, use_pallas
    )
    return loss


def _fused_ce_vjp_fwd(x, wte, targets, num_chunks, compute_dtype,
                      use_pallas):
    if use_pallas:
        loss, lse = _ce_fwd_pallas(x, wte, targets, compute_dtype)
        return loss, (x, wte, targets, lse)
    return _fused_ce_fwd(x, wte, targets, num_chunks, compute_dtype)


def _fused_ce_fwd(x, wte, targets, num_chunks, compute_dtype):
    V = wte.shape[0]
    wte_chunks, Vc = _chunk_wte(wte, num_chunks)

    def scan_body(carry, inp):
        m, s, gold = carry
        k, wc = inp
        offset = k * Vc
        logits = _chunk_logits(x, wc, offset, V, compute_dtype)
        cmax = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, cmax)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1
        )
        # Gold-label logit if the target falls in this chunk.
        shifted = targets - offset
        in_chunk = (shifted >= 0) & (shifted < Vc)
        picked = jnp.take_along_axis(
            logits, jnp.clip(shifted, 0, Vc - 1)[..., None], axis=-1
        )[..., 0]
        gold = jnp.where(in_chunk, picked, gold)
        return (m_new, s, gold), None

    # Derive the init carry from `targets` so it inherits the input's
    # varying-manual-axes type under shard_map (a constant init makes the
    # scan carry type mismatch its output inside a Manual-mesh region).
    zeros = (targets * 0).astype(jnp.float32)
    init = (zeros + _NEG_INF, zeros, zeros)
    (m, s, gold), _ = jax.lax.scan(
        scan_body, init, (jnp.arange(num_chunks), wte_chunks)
    )
    lse = m + jnp.log(s)
    loss = lse - gold
    return loss, (x, wte, targets, lse)


def _match_vma(val: jax.Array, ref: jax.Array) -> jax.Array:
    """psum ``val`` over manual mesh axes it varies over but ``ref`` does
    not.  Under shard_map the cotangent of a *replicated* (unvarying)
    primal must itself be unvarying — for built-in ops JAX inserts this
    psum when transposing the implicit ``pvary``; a custom_vjp bwd rule
    must do it by hand (VMA type checking rejects the rule otherwise)."""
    extra = tuple(sorted(_vma_of(val) - _vma_of(ref)))
    return jax.lax.psum(val, extra) if extra else val


def _fused_ce_bwd(num_chunks, compute_dtype, use_pallas, res, g):
    x, wte, targets, lse = res
    dx, dwte = _ce_bwd_core(
        x, wte, targets, lse, g, num_chunks, compute_dtype, use_pallas
    )
    return (
        _match_vma(dx.astype(x.dtype), x),
        _match_vma(dwte.astype(wte.dtype), wte),
        np.zeros(targets.shape, jax.dtypes.float0),
    )


def _ce_bwd_core(x, wte, targets, lse, g, num_chunks, compute_dtype,
                 use_pallas):
    """(dx, dwte) in f32, no vma handling — shared by the GSPMD custom
    vjp and the shard_map island."""
    V, d = wte.shape
    if use_pallas:
        return _ce_bwd_pallas(
            x, wte, targets, lse, g.astype(jnp.float32), compute_dtype
        )
    wte_chunks, Vc = _chunk_wte(wte, num_chunks)
    g32 = g.astype(jnp.float32)

    def scan_body(dx, inp):
        k, wc = inp
        offset = k * Vc
        logits = _chunk_logits(x, wc, offset, V, compute_dtype)
        p = jnp.exp(logits - lse[..., None])
        shifted = targets - offset
        onehot = (
            (shifted[..., None] == jnp.arange(Vc))
        ).astype(jnp.float32)
        dlogits = (p - onehot) * g32[..., None]
        dl_c = dlogits.astype(compute_dtype)
        dx = dx + jnp.einsum(
            "...v,vd->...d", dl_c, wc.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        dw_c = jnp.einsum(
            "...v,...d->vd", dl_c, x.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        return dx, dw_c

    dx, dw_chunks = jax.lax.scan(
        scan_body,
        x.astype(jnp.float32) * 0,  # varying-typed zeros (see fwd init)
        (jnp.arange(num_chunks), wte_chunks),
    )
    dwte = dw_chunks.reshape(num_chunks * Vc, d)[:V]
    return dx, dwte


_fused_ce.defvjp(_fused_ce_vjp_fwd, _fused_ce_bwd)


def fused_lm_head_cross_entropy(
    x: jax.Array,
    wte: jax.Array,
    targets: jax.Array,
    *,
    num_chunks: Optional[int] = None,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Per-token CE loss of the tied LM head, without materializing logits.

    Args:
        x: final hidden states ``(..., d)`` (any float dtype).
        wte: tied embedding table ``(V, d)``.
        targets: int labels, shape ``x.shape[:-1]``.
        num_chunks: vocab chunks to scan over (default: ~8192-wide chunks).
        compute_dtype: matmul input dtype (f32 accumulation regardless).
        use_pallas: run forward AND backward through the Pallas tile
            kernels (zero HBM logits traffic in both directions).
            Callers that know they are on one chip (no GSPMD-sharded
            operands — a ``pallas_call`` is opaque to the partitioner)
            opt in; default off falls back to the GSPMD-safe scan.

    Returns:
        float32 per-token losses, shape ``targets.shape``.
    """
    if num_chunks is None:
        num_chunks = _pick_num_chunks(wte.shape[0])
    pallas = bool(use_pallas) and _pallas_fwd_ok(
        x.shape[-1], compute_dtype
    )
    return _fused_ce(
        x, wte, targets, num_chunks, jnp.dtype(compute_dtype), pallas
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce_shmap(x, wte, targets, mesh, batch_axes, num_chunks,
                    compute_dtype, use_pallas):
    loss, _ = _fused_ce_shmap_fwd(
        x, wte, targets, mesh, batch_axes, num_chunks, compute_dtype,
        use_pallas,
    )
    return loss


def _fused_ce_shmap_fwd(x, wte, targets, mesh, batch_axes, num_chunks,
                        compute_dtype, use_pallas):
    from jax.sharding import PartitionSpec as P

    Pb = P(batch_axes)

    def local(xl, w, tl):
        if use_pallas:
            return _ce_fwd_pallas(xl, w, tl, compute_dtype)
        loss, (_, _, _, lse) = _fused_ce_fwd(
            xl, w, tl, num_chunks, compute_dtype
        )
        return loss, lse

    loss, lse = jax.shard_map(
        local, mesh=mesh, in_specs=(Pb, P(), Pb), out_specs=(Pb, Pb),
        check_vma=False,
    )(x, wte, targets)
    return loss, (x, wte, targets, lse)


def _fused_ce_shmap_bwd(mesh, batch_axes, num_chunks, compute_dtype,
                        use_pallas, res, g):
    from jax.sharding import PartitionSpec as P

    x, wte, targets, lse = res
    Pb = P(batch_axes)
    axes = tuple(a for spec in batch_axes
                 for a in (spec if isinstance(spec, tuple) else (spec,)))

    def local(xl, w, tl, lsel, gl):
        dxl, dwp = _ce_bwd_core(
            xl, w, tl, lsel, gl, num_chunks, compute_dtype, use_pallas
        )
        # check_vma=False shard_map does NOT insert the replicated-input
        # cotangent psum — do it explicitly (each device holds the
        # partial dwte of its batch shard).
        return dxl, jax.lax.psum(dwp, axes)

    dx, dwte = jax.shard_map(
        local, mesh=mesh,
        in_specs=(Pb, P(), Pb, Pb, Pb), out_specs=(Pb, P()),
        check_vma=False,
    )(x, wte, targets, lse, g.astype(jnp.float32))
    return (
        dx.astype(x.dtype),
        dwte.astype(wte.dtype),
        np.zeros(targets.shape, jax.dtypes.float0),
    )


_fused_ce_shmap.defvjp(_fused_ce_shmap_fwd, _fused_ce_shmap_bwd)


def fused_lm_head_cross_entropy_sharded(
    x: jax.Array,
    wte: jax.Array,
    targets: jax.Array,
    mesh,
    *,
    batch_axes: Optional[Tuple[str, ...]] = None,
    num_chunks: Optional[int] = None,
    compute_dtype: jnp.dtype = jnp.bfloat16,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Multi-chip fused CE: a shard_map island running the Pallas kernels
    per device (jit → shard_map → pallas, the canonical distributed-kernel
    pattern).

    Requirements: ``x``/``targets`` batch-sharded on dim 0 over
    ``batch_axes`` and ``wte`` fully replicated (pure DP / ZeRO-1/2 —
    NOT tensor-sharded heads or ZeRO-3). Each device runs the kernel on
    its local tokens against the full vocab; the only collective is one
    psum of the dwte partials in the backward — identical math to the
    GSPMD scan path, minus every chunk intermediate's HBM round-trip.

    Runs the scan inside the island when the shape gate rejects, so
    callers can use it unconditionally for replicated-head meshes.
    """
    if batch_axes is None:
        batch_axes = tuple(
            a for a in mesh.axis_names if a in ("data", "fsdp")
        )
    if not batch_axes:
        raise ValueError(
            f"no batch axes among mesh axes {mesh.axis_names}"
        )
    n_shards = 1
    for a in batch_axes:
        n_shards *= mesh.shape[a]
    if x.shape[0] % n_shards:
        raise ValueError(
            f"batch dim {x.shape[0]} not divisible by "
            f"{batch_axes}={n_shards}"
        )
    if num_chunks is None:
        num_chunks = _pick_num_chunks(wte.shape[0])
    pallas = use_pallas is not False and _pallas_fwd_ok(
        x.shape[-1], compute_dtype
    )
    return _fused_ce_shmap(
        x, wte, targets, mesh, tuple(batch_axes), num_chunks,
        jnp.dtype(compute_dtype), pallas,
    )


def naive_lm_head_cross_entropy(
    x: jax.Array, wte: jax.Array, targets: jax.Array,
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Reference path: full ``(..., V)`` f32 logits + softmax CE.  Used
    for parity tests and as the small-vocab fallback."""
    import optax

    logits = jnp.einsum(
        "...d,vd->...v",
        x.astype(compute_dtype), wte.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return optax.softmax_cross_entropy_with_integer_labels(logits, targets)
