"""Pallas TPU flash-attention (causal): forward + fused backward kernels.

The hot op of the transformer family, written TPU-first per the Pallas
playbook (``/opt/skills/guides/pallas_guide.md``):

* K/V of a head live in VMEM and are walked with online softmax — memory
  is O(seq · head_dim) instead of the O(seq²) logits tensor, in both
  directions, at any context length;
* **score tiles are held transposed, ``(keys, queries)``**: the queries
  lie along the lanes, so the per-query statistics (running max ``m``,
  denominator ``l``, ``lse``, ``delta``) are lane-dense ``(1, queries)``
  rows, a reduction over the keys is an elementwise max / add down the
  sublanes, and no statistic is ever broadcast across lanes.  In the
  backward ``dv += p-tile @ dO`` and ``dk += ds-tile @ q`` are plain
  matmuls of the tile as it lies (the ``(queries, keys)`` form transposes
  ``p`` and ``ds`` for them);
* the causal structure bounds the walk twice: key tiles wholly above a
  query tile are never visited, and the square ON the diagonal is walked
  in 128-wide strips bounded by the diagonal, so that of a 1024 x 1024
  square 36 of 64 sub-squares are multiplied and 8 take the mask;
* a backward program is one head and as many whole key tiles as fit
  VMEM: ``dq`` then accumulates in float32 inside the program and the
  partials output is one plane a program (ONE plane, and no sum outside,
  at the fit cell's shapes);
* logits, statistics and accumulators in float32, matmul operands in the
  caller's dtype (bfloat16 in the mixed-precision recipe), probabilities
  rounded to it where they meet ``v`` / ``dO``; a scale that is a power
  of two (0.125 at head width 64) is folded into q, exactly;
* the forward emits ``lse = m + log l`` as ``(BH, S, 8)`` float32;
  ``delta = rowsum(dO · O)`` is computed in-kernel from the O block.

Block shapes come from what the kernel can observe (``_pick_walk``: the
sequence, the widths, the dtype, the VMEM the blocks need): no argument,
field or environment variable chooses them.

Measured, kernel alone on one TPU v5e, device time a call from a trace,
q / k / v bf16 ``(128, 1024, 64)`` (the ``gpt2-medium.fit`` cell's; my chip
runs, PR 31; ``tools/flash_sweep.py``; the whole table is in ``PERF.md``
section 6):

====================================================  =======  ========
walk                                                  forward  backward
====================================================  =======  ========
before PR 31, ``(queries, keys)`` tiles, 512 x 512    669.5    1024.1
  the same, 256 x 512 / 256 x 256                     657.6 /  1128.8 /
                                                      925.5    1732.4
transposed, 512 tiles, 128-wide strips as below       484.8    772.8
transposed, 1024 tiles, 128-row key strips            390.1    738.0
transposed, 1024 tiles, 128-lane query strips         608.1    719.2
transposed, 1024 tiles, no strips (whole square)      480.4    not run
====================================================  =======  ========

(us a call; 87 and 174 us of FLOPs at the chip's peak, causal half.)  The
forward walks the diagonal square in key strips (a key sub-block against
the query lanes at or past it: wide tiles, whose reductions down the
sublanes run as many independent chains as there are lanes), the backward
in query strips (a query sub-block against the keys at or above it: long
streams through the same weights for four of its five matmuls).  Four
heads unrolled in a forward program measured 344.5, but every process
lowers a kernel's whole text at set-up, compile cache or not, and the two
serve cells' warm ``setup_s`` rose 14-20% with it; four heads as a loop
measured 392.9, no better than one: so a program is one head.  At the
serve cells' prefill shapes the primal forward measured 1500 us against
1760 (64 heads, 3072, width 128) and 7066 against 7688 (64, 6144, 192 with
values of 128).

(The reference framework has no analogue — its compute is opaque torch
modules; this file exists because the TPU build owns its model math.)
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"]

# Tiles of 1024 where they divide the sequence: at the fit cell's shapes the
# kernels measured 390.1 us forward / 719.2 backward a call against 484.8 /
# 772.8 with tiles of 512 (my chip runs, PR 31; the table above).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

_NEG_INF = -1e30
# Lane quantum: a score tile is (keys, queries), queries along the lanes,
# so both block sizes are whole multiples of it.
_LANE = 128
# Width of one strip of the diagonal square: key rows in the forward,
# query lanes in the backward (see the kernels).
_SUB = 128
# HBM width of the per-row lse stat.  In VMEM the tile is lane-padded
# anyway, but the HBM array is (BH, S, _STAT_W) — at 128 the saved-
# residual traffic was ~100 MB/layer of 128x-redundant f32 (the single
# largest line in the step profile); 8 keeps a legal f32 tile while
# cutting that 16x.
_STAT_W = 8
# VMEM a kernel may take, and what of it a program's blocks and scratch
# may take by ``_tile_bytes``' count (the rest is for the score tiles'
# temporaries).  The compiler's scoped default of 16 MiB is 0.5 MiB short
# of a 1024 x 1024 tile beside sarvam's K and V of 6144 x 192; XLA plans
# its own use of VMEM round the largest limit a program's kernels ask
# for, and the fit cell's step grows in HBM with it (13.78 GB at 16 MiB,
# 13.81 at 32, 13.95 at 64: sandbox compiles, PR 31), so no more than
# the walks need.
_VMEM_LIMIT = 32 * 2**20
_VMEM_BLOCKS = 16 * 2**20

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


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


class _Walk(NamedTuple):
    """The tile walk of one call, chosen from its shapes alone."""
    block_q: int    # forward: the query tile of a program
    block_k: int    # backward: a key tile
    step: int       # the other side's step below the diagonal square:
    #                 keys in the forward, queries in the backward
    sub: int        # width of a strip of the diagonal square
    span: int       # backward: keys to a program (a multiple of block_k)
    fold: bool      # scale is a power of two: folded into q, exactly


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) block: lanes padded to 128, sublanes
    to a whole packed tile."""
    sublanes = 8 * (4 // itemsize)
    return (-(-rows // sublanes) * sublanes) * (-(-cols // _LANE) * _LANE) \
        * itemsize


def _pick_walk(s: int, d: int, itemsize: int, scale: float,
               block_q: Optional[int], block_k: Optional[int]) -> _Walk:
    """Block shapes from what the kernel can observe: the sequence, the
    q / k width, the dtype, and the VMEM the backward's blocks need (the
    forward's are K and V of one head and a query tile: whatever fits the
    backward fits it, and serving shapes are compiled by
    ``tests/test_chip_compile.py``)."""
    if block_q is None:
        block_q = pick_block(s) or min(DEFAULT_BLOCK_Q, s)
    if block_k is None:
        block_k = pick_block(s, DEFAULT_BLOCK_K) or min(DEFAULT_BLOCK_K, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"seq_len {s} must be divisible by block_q={block_q} and "
            f"block_k={block_k}"
        )
    if block_k % _LANE or block_q % _LANE:
        raise ValueError(
            f"block_q={block_q} and block_k={block_k} must each be a "
            f"multiple of {_LANE} (lane quantum of the score tiles)"
        )
    # Each side's step divides the other's tile, so that a diagonal square
    # is whole steps.
    step = math.gcd(block_q, block_k)
    # Backward: q, dO, O, lse and the dq plane of a head stay resident
    # (double buffered) beside the float32 dq scratch; a program takes as
    # many whole key tiles (k, v, dk, dv) as fit, four at most (the walk
    # over them is unrolled).
    whole = 2 * (4 * _tile_bytes(s, d, itemsize)
                 + _tile_bytes(s, _STAT_W, 4)) + _tile_bytes(d, s, 4)
    span = block_k
    for n in (4, 3, 2):
        if (s % (n * block_k) == 0 and whole + 8 * _tile_bytes(
                n * block_k, d, itemsize) <= _VMEM_BLOCKS):
            span = n * block_k
            break
    fold = math.frexp(scale)[0] == 0.5
    return _Walk(block_q, block_k, step, min(_SUB, step), span, fold)


def _diag_bias(sub: int) -> jax.Array:
    """(keys, queries) additive mask of a diagonal sub-square."""
    key = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    qry = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    return jnp.where(qry >= key, 0.0, _NEG_INF).astype(jnp.float32)


def _masked(s, bias, axis):
    """Add the diagonal square's mask to a strip of scores ``(keys,
    queries)``: to its first query lanes where the strip is a key
    sub-block with the lanes to its right (``axis`` 1), to its last key
    rows where it is a query sub-block with the keys above it (0)."""
    sub, size = bias.shape[0], s.shape[axis]
    if size == sub:
        return s + bias
    cut = sub if axis == 1 else size - sub
    a = jax.lax.slice_in_dim(s, 0, cut, axis=axis)
    b = jax.lax.slice_in_dim(s, cut, size, axis=axis)
    return jax.lax.concatenate(
        [a + bias, b] if axis == 1 else [a, b + bias], axis)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, walk, want_lse):
    """One program: one head x one query tile.

    A score tile is held TRANSPOSED, ``(keys, queries)``: the queries lie
    along the lanes, so the running max ``m``, the denominator ``l`` and
    their corrections are ``(1, block_q)`` lane-dense rows, every
    reduction over the keys is an elementwise max / add down the
    sublanes, and the accumulator ``(dv, block_q)`` fills its lanes at
    any value width.  The tile is transposed back once, at the store.

    Keys wholly below the query tile are walked in ``block_k`` rows,
    unmasked.  The diagonal square is walked in key sub-blocks of
    ``sub`` rows, each against only the query lanes at or past it: what
    lies wholly above the diagonal is never multiplied, and only the
    ``sub x sub`` square on the diagonal takes the mask.

    MXU discipline: dot inputs stay in the CALLER's dtype (bf16 in the
    mixed-precision recipe); accumulation is f32 via
    preferred_element_type, and the statistics never leave f32.  A scale
    that is a power of two is folded into q (exact in any float dtype);
    any other multiplies the f32 scores.
    """
    if want_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    block_q, block_k, sub = walk.block_q, walk.step, walk.sub
    q_base = pl.multiple_of(pl.program_id(1) * block_q, block_q)
    bias = _diag_bias(sub)

    q = q_ref[0]                                      # (block_q, d)
    if walk.fold:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def visit(start, rows, lo, n, masked):
        """Keys [start, start + rows) against query lanes [lo, lo + n)."""
        k_blk = k_ref[0, pl.ds(start, rows), :]
        v_blk = v_ref[0, pl.ds(start, rows), :]
        s = jax.lax.dot_general(
            k_blk, jax.lax.slice_in_dim(q, lo, lo + n), _NT,
            preferred_element_type=jnp.float32)
        if not walk.fold:
            s = s * scale
        if masked:
            s = _masked(s, bias, 1)
        m_prev = m_ref[:, lo:lo + n]
        m_new = jax.lax.max(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jax.lax.exp(s - m_new)
        corr = jax.lax.exp(m_prev - m_new)
        l_ref[:, lo:lo + n] = l_ref[:, lo:lo + n] * corr + jnp.sum(
            p, axis=0, keepdims=True)
        acc_ref[:, lo:lo + n] = (
            acc_ref[:, lo:lo + n] * corr + jax.lax.dot_general(
                v_blk, p.astype(v_blk.dtype), _TN,
                preferred_element_type=jnp.float32))      # (dv, n)
        m_ref[:, lo:lo + n] = m_new

    def below(kb, carry):
        visit(pl.multiple_of(kb * block_k, block_k), block_k, 0, block_q,
              False)
        return carry

    jax.lax.fori_loop(0, q_base // block_k, below, 0)
    for c in range(block_q // sub):
        visit(q_base + c * sub, sub, c * sub, block_q - c * sub, True)

    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / l).T.astype(o_ref.dtype)
    if want_lse:
        lse_ref[0] = jnp.broadcast_to(
            m_ref[...] + jnp.log(l), (_STAT_W, block_q)).T


def _interpret() -> bool:
    from ray_lightning_tpu.ops.kernel_probe import _interpret as shared

    return shared()


@functools.partial(  # rlt: noqa[RLT008] traced into the caller's program, never an executable of its own
    jax.jit, static_argnames=("scale", "walk", "want_lse"))
def _flash_fwd_bhsd(q, k, v, scale, walk, want_lse=True):
    """q/k/v: (BH, S, D) merged batch-heads layout -> (out, lse|None).

    Jitted so that the layers of an unrolled model share ONE lowered
    kernel (a model of 8 layers lowered 8 kernels a program, seconds of
    every process's set-up whether or not its compile cache is warm).

    ``want_lse=False`` (the primal, non-differentiated path — eval/
    predict) compiles a forward-only kernel with a single output, so no
    O(BH·S·lane) f32 lse tensor is allocated or written.
    """
    bh, s, d = q.shape
    dv = v.shape[-1]    # the values' own width (the accumulator's)
    block_q = walk.block_q
    out_shape = jax.ShapeDtypeStruct((bh, s, dv), q.dtype)
    out_spec = pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0))
    lse_spec = pl.BlockSpec((1, block_q, _STAT_W), lambda b, i: (b, i, 0))
    result = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, walk=walk, want_lse=want_lse),
        out_shape=(
            out_shape,
            jax.ShapeDtypeStruct((bh, s, _STAT_W), jnp.float32),
        ) if want_lse else out_shape,
        grid=(bh, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=(out_spec, lse_spec) if want_lse else out_spec,
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),    # m
            pltpu.VMEM((1, block_q), jnp.float32),    # l
            pltpu.VMEM((dv, block_q), jnp.float32),   # out, transposed
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="rlt_flash_fwd",
    )(q, k, v)
    return result if want_lse else (result, None)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dk_ref,
                dv_ref, dqp_ref, stat_ref, dq_ref, dk_acc, dv_acc, kt_ref,
                *, scale, walk, seq_len):
    """One program: one head x ``walk.span`` keys (whole key tiles).

    Score tiles are held transposed, ``(keys, queries)``, as in the
    forward: ``lse`` and ``delta = rowsum(dO · O)`` are transposed once a
    program into lane-dense rows; ``dv += p^T-tile @ dO`` and ``dk +=
    ds^T-tile @ q`` are plain matmuls of the tile as it lies (no
    transposed copy of the probabilities), and ``dq`` accumulates
    transposed, ``(d, queries)`` in float32 across the program's key
    tiles, and is transposed back once.  One recomputation of s / p / dp
    / ds serves all three gradients: 5 MXU dots a tile pair.

    Each key tile walks its diagonal square in query strips of ``sub``
    lanes, each against only the tile's keys at or above it (the mask on
    the strip's last ``sub`` rows), then the query tiles wholly below it,
    unmasked.  The program's dq is ONE plane of the partials output: the
    planes of a head's programs are summed outside (one plane, no sum,
    where a head is one program).  ``kt_ref`` holds the key tile
    transposed for the dq matmul: the TPU compiler refuses an operand
    that one matmul takes as it lies and another transposed.
    """
    block_q, block_k, sub = walk.step, walk.block_k, walk.sub
    span, fold = walk.span, walk.fold
    d = q_ref.shape[-1]
    k_base = pl.multiple_of(pl.program_id(1) * span, span)
    n_q = seq_len // block_q
    first_q = k_base // block_q
    bias = _diag_bias(sub)

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    def prepare(i, carry):
        # lse and delta of query tile i as (1, block_q) rows; delta in-
        # kernel: a few VPU ops on resident data instead of an
        # O(S·lane) f32 HBM round-trip per layer.
        rows = rows_of(i)
        delta = jnp.sum(
            do_ref[0, rows, :].astype(jnp.float32)
            * o_ref[0, rows, :].astype(jnp.float32), axis=1, keepdims=True)
        stat_ref[i, 0:1, :] = lse_ref[0, rows, :].T[:1]
        stat_ref[i, 1:2, :] = jnp.broadcast_to(
            delta, (block_q, _STAT_W)).T[:1]
        dq_ref[i] = jnp.zeros((d, block_q), jnp.float32)
        return carry

    jax.lax.fori_loop(first_q, n_q, prepare, 0)

    def load_q(i):
        q = q_ref[0, rows_of(i), :]
        if fold:
            q = (q.astype(jnp.float32) * scale).astype(q.dtype)
        return q

    def pair(k_c, v_c, i, q_t, do_t, lo, n, masked):
        """The tile's first key rows (those of ``k_c``) against lanes
        [lo, lo + n) of query tile ``i``."""
        rows = k_c.shape[0]
        q_sl = jax.lax.slice_in_dim(q_t, lo, lo + n)
        do_sl = jax.lax.slice_in_dim(do_t, lo, lo + n)
        s = jax.lax.dot_general(
            k_c, q_sl, _NT, preferred_element_type=jnp.float32)
        if not fold:
            s = s * scale
        if masked:
            s = _masked(s, bias, 0)
        p = jax.lax.exp(s - stat_ref[i, 0:1, lo:lo + n])  # (rows, n)
        dv_acc[:rows] += jnp.dot(
            p.astype(do_sl.dtype), do_sl,
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            v_c, do_sl, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - stat_ref[i, 1:2, lo:lo + n])
        if not fold:
            ds = ds * scale
        ds = ds.astype(q_sl.dtype)
        # Folded: q_sl carries the scale, so dk is exact as it stands and
        # dq takes its scale in float32 at the store.
        dk_acc[:rows] += jnp.dot(
            ds, q_sl, preferred_element_type=jnp.float32)
        dq_ref[i, :, lo:lo + n] += jnp.dot(
            kt_ref[:, :rows], ds, preferred_element_type=jnp.float32)

    for jj in range(span // block_k):
        k_t = k_ref[0, jj * block_k:(jj + 1) * block_k, :]
        v_t = v_ref[0, jj * block_k:(jj + 1) * block_k, :]
        kt_ref[...] = k_t.T
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        tile_q = (k_base + jj * block_k) // block_q   # first query tile
        for t in range(block_k // block_q):
            i = tile_q + t
            q_t, do_t = load_q(i), do_ref[0, rows_of(i), :]
            for c in range(block_q // sub):
                rows = t * block_q + (c + 1) * sub    # keys the strip sees
                pair(jax.lax.slice_in_dim(k_t, 0, rows),
                     jax.lax.slice_in_dim(v_t, 0, rows), i, q_t, do_t,
                     c * sub, sub, True)

        def below(i, carry, k_t=k_t, v_t=v_t):
            pair(k_t, v_t, i, load_q(i), do_ref[0, rows_of(i), :], 0,
                 block_q, False)
            return carry

        jax.lax.fori_loop(tile_q + block_k // block_q, n_q, below, 0)
        dk_ref[0, jj * block_k:(jj + 1) * block_k, :] = dk_acc[...].astype(
            dk_ref.dtype)
        dv_ref[0, jj * block_k:(jj + 1) * block_k, :] = dv_acc[...].astype(
            dv_ref.dtype)

    # Query tiles before the causal frontier take nothing from these keys.
    def dead(i, carry):
        dqp_ref[0, 0, rows_of(i), :] = jnp.zeros((block_q, d), dqp_ref.dtype)
        return carry

    def store(i, carry):
        dq = dq_ref[i] * scale if fold else dq_ref[i]
        dqp_ref[0, 0, rows_of(i), :] = dq.T.astype(dqp_ref.dtype)
        return carry

    jax.lax.fori_loop(0, first_q, dead, 0)
    jax.lax.fori_loop(first_q, n_q, store, 0)


@functools.partial(  # rlt: noqa[RLT008] traced into the caller's program, as the forward
    jax.jit, static_argnames=("scale", "walk"))
def _flash_bwd_bhsd(q, k, v, out, lse, g, scale, walk):
    """Backward over (BH, S, D) tensors; returns (dq, dk, dv).  Jitted
    for the same reason as the forward."""
    bh, s, d = q.shape
    step, span = walk.step, walk.span
    n = s // span
    whole = lambda width: pl.BlockSpec((1, s, width), lambda b, i: (b, 0, 0))
    keys = pl.BlockSpec((1, span, d), lambda b, i: (b, i, 0))
    dk, dv, dqp = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, walk=walk, seq_len=s),
        out_shape=(
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
            # dq partials, a plane a program, in the input dtype: a plane
            # is a float32 accumulation over its program's keys rounded
            # once, and the cross-program sum below runs in f32.
            jax.ShapeDtypeStruct((bh, n, s, d), q.dtype),
        ),
        grid=(bh, n),
        in_specs=[whole(d), keys, keys, whole(d), whole(_STAT_W), whole(d)],
        out_specs=(
            keys, keys,
            pl.BlockSpec((1, 1, s, d), lambda b, i: (b, i, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((s // step, 2, step), jnp.float32),  # lse, delta
            pltpu.VMEM((s // step, d, step), jnp.float32),  # dq, transposed
            pltpu.VMEM((walk.block_k, d), jnp.float32),     # dk of a tile
            pltpu.VMEM((walk.block_k, d), jnp.float32),     # dv of a tile
            pltpu.VMEM((d, walk.block_k), k.dtype),         # key tile^T
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="rlt_flash_bwd",
    )(q, k, v, g, lse, out)
    if n == 1:
        return dqp[:, 0], dk, dv
    dq = jnp.sum(dqp.astype(jnp.float32), axis=1).astype(q.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _flash(scale, walk, q, k, v):
    b, s, h, _ = q.shape

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, x.shape[-1])

    out, _ = _flash_fwd_bhsd(
        to_bhsd(q), to_bhsd(k), to_bhsd(v), scale, walk, want_lse=False,
    )
    return out.reshape(b, h, s, v.shape[-1]).transpose(0, 2, 1, 3)


def _flash_vjp_fwd(scale, walk, q, k, v):
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
    out, lse = _flash_fwd_bhsd(qm, km, vm, scale, walk)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (
        out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
        (qm, km, vm, out, lse, (b, s, h, d)),
    )


def _flash_vjp_bwd(scale, walk, residuals, g):
    qm, km, vm, out, lse, (b, s, h, d) = residuals
    gm = g.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    dq, dk, dv = _flash_bwd_bhsd(qm, km, vm, out, lse, gm, scale, walk)

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
    b, s, h, d = q.shape
    scale = float((d ** -0.5) if scale is None else scale)
    walk = _pick_walk(s, d, q.dtype.itemsize, scale, block_q, block_k)
    return _flash(scale, walk, q, k, v)
