"""Pallas TPU paged decode attention: one query token per slot against
the KV blocks that are resident for it, read where they lie in the pool.

The serving cache (``serve/kv_cache.py``) is one pool per tensor,
``(L, N, Bs, H*Dh)``: a physical block of one layer is ``Bs`` rows of
all heads side by side, one contiguous DMA.  A decode tick attends one
new token per slot over positions ``[0, seq_len]``.  The XLA path
gathers every slot's whole block table (``M x Bs`` positions) and masks
afterwards; this kernel

* takes ``block_tables``, the number of cache positions each slot holds
  and the layer index by scalar prefetch, and walks per slot only the
  ``ceil(seq_len / Bs)`` blocks that hold them (none for an inactive
  slot), in chunks of ``CHUNK_POSITIONS`` positions, double-buffered:
  the next chunk (of this slot, or the first of the next slot) is in
  flight while the current one is computed, its copies a loop over the
  resident blocks only;
* computes every chunk of a slot but its last whole, one online-softmax
  step, and the last over the ``TAIL_POSITIONS``-position sub-tiles that
  hold the slot's positions only (one static width a branch): a step's
  fixed cost (its copies waited and started, a loop turn, two matmul
  fills, the accumulator rescaled) is paid once per ``CHUNK_POSITIONS``
  positions, while a slot still computes fewer than ``TAIL_POSITIONS``
  masked positions a layer.  The value rows such a tail reads past the
  blocks just copied are zeroed where no copy of the call has reached
  them (a masked position contributes 0 x row), each at most once a
  call;
* leaves the pool in HBM (``memory_space=pl.ANY``) and never writes it:
  the current token's own K and V rows arrive as operands and open the
  online softmax as position ``seq_len``, so the caller scatters all
  layers' rows into the pool once, after the layer loop;
* computes per-head scores from the ``(T, H*Dh)`` tile on the MXU with
  a block-diagonal query ``(H, H*Dh)`` (row ``h`` holds head ``h``'s
  query in its own ``Dh`` columns, zeros elsewhere), and reads the
  per-head output off the diagonal blocks of ``P @ V``;
* keeps the XLA path's arithmetic: products of the stored values in
  float32, softmax statistics and the accumulator in float32.  A float32
  operand meeting a bfloat16 pool is split into three bfloat16 terms
  (exact: 3 x 8 significand bits), stacked on the rows of ONE matmul, so
  the probabilities are never rounded to bfloat16 before they meet V.

The same kernel body, under its own name ``rlt_ring_decode``, serves a
window layer's decode (``models/exaone_moe.py``): the slot's table is
its ring of ``ceil(window / Bs) + 1`` blocks, read whole as one step, a
ring location masked by the absolute position it holds; where the layer
has a sink (a logit a query head with no value) it is folded into the
softmax's statistics once, after the ring.

A second kernel, ``rlt_mla_decode``, serves latent (MLA) attention
(``models/sarvam_mla.py``): one cached row ``[c | k_r | 0]`` a position
shared by all heads, scored against the heads' absorbed queries as one
matmul of ``H`` rows; the tile is read from HBM once and serves as keys
(every column) and as values (its first ``rank`` columns).  The walk of
the block table, the chunking, the double-buffered copies and the
online softmax are the helpers both kernels call.

Off-TPU the kernels run under the Pallas interpreter (tests only: the
serving path takes them on TPU alone, see :func:`paged_decode_supported`
and :func:`mla_decode_supported`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.attention import _NEG_INF
from ray_lightning_tpu.ops.kernel_probe import (
    _interpret, kernel_family_disabled,
)

__all__ = ["paged_decode_attention", "paged_decode_supported",
           "ring_decode_attention", "ring_decode_supported",
           "mla_decode_attention", "mla_decode_supported", "walk_plan"]

# Cache positions one step of the walk copies and computes (whole
# blocks), in every chunk of a slot but its last; both kernels.  What a
# wide step buys back is its fixed cost.  Kernel alone, a tick's layers
# summed, before PR 35 (128 a step; latent 512) -> 1024 with the tail
# trimmed (my chip runs, PR 35; PERF.md section 6): GPT-2's 16 slots of
# ~420 positions 2.67 -> 2.03 ms (512: 2.17), K-EXAONE's full layers
# 0.45 -> 0.34, sarvam's latent layers 3.73 -> 3.32 (512: 3.76); slots
# of 33 and 100 positions 0.600 -> 0.620 and 0.791 -> 0.788.
CHUNK_POSITIONS = 1024
# The last chunk is computed over whole sub-tiles of this many positions
# (whole lanes of the score tile), only those that hold the slot's
# positions: at most TAIL_POSITIONS - 1 masked positions a slot a layer.
# Computed whole instead, 1024 would cost GPT-2's cell 2.32 ms and slots
# of 33 positions 1.72 (the same runs).
TAIL_POSITIONS = 128


def _vmem_limit(buffer_bytes: int) -> Optional[int]:
    """The scoped VMEM a walk kernel asks for: the compiler's default
    (16 MiB on a v5e) where its double buffers leave a step's tiles
    room under it (bfloat16 rows of 1280 at 1024 positions: 10 MiB of
    buffers), else the buffers and room for float32 tiles (float32 rows
    of 1280: 20 MiB and 4.7 more).  No more than that: XLA plans its own
    VMEM round the largest limit a program's kernels ask for
    (``ops/flash_attention.py``)."""
    mib = 2**20
    return None if buffer_bytes <= 12 * mib else buffer_bytes + 8 * mib


def _sublane(dtype) -> int:
    return 16 if dtype == jnp.bfloat16 else 8


def paged_decode_supported(pool_k: jax.Array) -> bool:
    """Whether ``attn_impl="auto"`` takes the kernel: a function of the
    backend, the pool's shape and dtype and ``RLT_DISABLE_KERNELS``
    (family ``paged``) — nothing is compiled to find out (the kernel's
    own refusals are caught by ``tests/test_chip_compile.py``)."""
    if kernel_family_disabled("paged"):
        return False
    if jax.default_backend() != "tpu":
        return False
    return paged_decode_tiles(pool_k)


def paged_decode_tiles(pool_k: jax.Array) -> bool:
    """Shapes and dtypes the kernel tiles: ``(L, N, Bs, H*Dh)`` with the
    row of all heads a multiple of the 128 lanes and a block a whole
    number of the dtype's sublane tiles."""
    if pool_k.ndim != 4 or pool_k.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    _, _, bs, hd = pool_k.shape
    return hd % 128 == 0 and bs % _sublane(pool_k.dtype) == 0


def _pages_per_chunk(m: int, bs: int, positions: int) -> int:
    """Blocks of a chunk: as many as cover ``positions`` positions, at
    most the table width ``m``.  It need not divide ``m``: a slot's walk
    stops at its table's end (``_table_walk`` ``n_vis``), so the last
    chunk of a full table holds what is left of it."""
    return max(1, min(m, positions // bs))


def _tail_width(width: int, bs: int) -> int:
    """The sub-tile of a chunk ``width`` positions wide that its last
    chunk is trimmed to: ``TAIL_POSITIONS`` where whole blocks make it
    and it divides the chunk, else the whole chunk (a table narrower
    than a sub-tile)."""
    t = TAIL_POSITIONS
    return t if width % t == 0 and t % bs == 0 else width


def walk_plan(seq_lens, table_width: int,
              block_size: int) -> Tuple[int, int]:
    """``(steps, positions computed)`` of one walk kernel's call, summed
    over the slots of ``seq_lens`` (positions already in the pool, as
    the kernel is handed them): a step is one online-softmax update, a
    whole chunk or the trimmed last one; an idle slot (0) has none.  The
    widths are the kernels' own, read from the same constants
    (:func:`_pages_per_chunk`, :func:`_tail_width`); host integers, for
    the engine's counters."""
    M, Bs = table_width, block_size
    width = _pages_per_chunk(M, Bs, CHUNK_POSITIONS) * Bs
    tail = _tail_width(width, Bs)
    lens = np.minimum(np.asarray(seq_lens, np.int64), M * Bs)
    whole = np.maximum(lens - 1, 0) // width   # chunks before the last
    rem = lens - whole * width
    steps = whole + (rem > 0)
    positions = whole * width + -(-rem // tail) * tail
    return int(steps.sum()), int(positions.sum())


def _split3(a: jax.Array) -> jax.Array:
    """float32 ``(R, C)`` -> bfloat16 ``(3R, C)`` whose three row groups
    sum to ``a`` exactly."""
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _dot_f32(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """``a . b`` with every product exact in float32 and float32
    accumulation, whatever the operands' dtypes (see module docstring)."""
    if b.dtype == jnp.float32:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b, dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    if a.dtype == b.dtype:
        return jax.lax.dot_general(
            a, b, dims, preferred_element_type=jnp.float32
        )
    r = a.shape[0]
    parts = jax.lax.dot_general(
        _split3(a), b, dims, preferred_element_type=jnp.float32
    )
    return (parts[2 * r:] + parts[r:2 * r]) + parts[:r]


def _table_walk(tables_ref, lens_ref, layer, streams, sems, *, pages,
                table_width, block_size):
    """The walk of a slot's block table that both decode kernels share.
    ``streams`` pairs each pool in HBM with its VMEM buffer ``(2, P*Bs,
    row)``; every stream follows the same table.  Returns ``(n_vis,
    n_pages, chunk_dma)``: the visible positions and the resident pages
    of a slot, and the start or wait of the copies of one of its
    chunks."""
    P, M, Bs = pages, table_width, block_size

    def n_vis(slot):
        # A length past the table (the draft chain's strays) walks the
        # table: no chunk holds more than its width.
        return jnp.minimum(lens_ref[slot], M * Bs)

    def n_pages(slot):
        return pl.cdiv(n_vis(slot), Bs)

    def chunk_dma(slot, c, buf, op):
        """Start or wait the copies of chunk ``c`` of ``slot``: its
        resident pages only (the rest of the buffer keeps older rows,
        which the mask hides), in a loop as long as they are: a step
        costs its slot's pages, not its width's."""
        lo = c * P

        def page(j, _):
            blk = tables_ref[slot * M + j]
            at = pl.ds(pl.multiple_of((j - lo) * Bs, Bs), Bs)
            for s, (hbm, vmem) in enumerate(streams):
                copy = pltpu.make_async_copy(
                    hbm.at[layer, blk], vmem.at[buf, at], sems.at[s, buf])
                getattr(copy, op)()

        jax.lax.fori_loop(lo, jnp.minimum(lo + P, n_pages(slot)), page, None)

    return n_vis, n_pages, chunk_dma


def _open_walk(w, marks, chunk_dma):
    """Before the first slot: the walk on buffer 0, no row of either
    buffer known finite, the first chunk in flight.  ``marks`` (SMEM,
    3 int32) carries across the grid's steps: the buffer the next slot
    starts on, then how many leading rows of each buffer are finite."""
    @pl.when(w == 0)
    def _():
        for i in range(3):
            marks[i] = 0
        chunk_dma(0, 0, 0, "start")


def _walk_chunks(w, n_slots, n_vis, n_pages, chunk_dma, marks, attend,
                 values, carry, *, pages, block_size):
    """``carry`` through slot ``w``'s chunks of ``pages`` blocks,
    double-buffered: while one is computed the next is in flight, this
    slot's or the first of the next slot.  Every chunk but the last is
    whole: ``attend(buf, width, carry)`` over its ``width`` positions at
    once.  The last holds the slot's remaining ``rem`` positions (0 for
    an idle slot; at most ``width``, as ``n_vis`` is at most the table,
    so the rows cleared below lie in the buffer) and is computed over
    the first ``ceil(rem / tail) * tail`` only (:func:`_tail_width`),
    one static width a branch: ``attend(buf, k * tail, carry, rem)``
    masks from ``rem`` on.  The
    rows it reads past the pages just copied must be finite in
    ``values`` (a masked position contributes 0 x row): those no copy or
    clearing of this call has reached yet are zeroed, a block at a time,
    so a call clears each row of a buffer at most once."""
    Bs = block_size
    width = pages * Bs
    tail = _tail_width(width, Bs)
    n_chunks = jnp.maximum(pl.cdiv(n_pages, pages), 1)

    def fetch(c, buf):
        chunk_dma(w, c, buf, "wait")

        @pl.when(c + 1 < n_chunks)
        def _():
            chunk_dma(w, c + 1, 1 - buf, "start")

        @pl.when((c + 1 == n_chunks) & (w + 1 < n_slots))
        def _():
            chunk_dma(w + 1, 0, 1 - buf, "start")

    def body(c, state):
        carry, buf = state
        fetch(c, buf)
        marks[1 + buf] = width
        return attend(buf, width, carry), 1 - buf

    last = n_chunks - 1
    carry, buf = jax.lax.fori_loop(0, last, body, (carry, marks[0]))
    fetch(last, buf)
    rem = n_vis - last * width
    k = pl.cdiv(rem, tail)              # the tail's sub-tiles
    need = k * tail

    def clear(p, _):
        for v in values:
            v[buf, pl.ds(pl.multiple_of(p * Bs, Bs), Bs)] = jnp.zeros(
                (Bs,) + v.shape[2:], v.dtype)

    finite = marks[1 + buf]
    jax.lax.fori_loop(jnp.maximum(finite, pl.cdiv(rem, Bs) * Bs) // Bs,
                      need // Bs, clear, None)
    marks[1 + buf] = jnp.maximum(finite, need)
    marks[0] = 1 - buf

    def trimmed(j):
        return lambda carry: attend(buf, j * tail, carry, rem)

    return jax.lax.switch(
        k, [lambda carry: carry] + [trimmed(j)
                                    for j in range(1, width // tail + 1)],
        carry)


def _ring_walk(w, n_slots, chunk_dma, values, attend, carry, *, width):
    """``carry`` through slot ``w``'s ring, one step of ``width``
    positions, double-buffered: slot ``w``'s copies were started into
    buffer ``w % 2`` while slot ``w - 1`` was computed, and the next
    slot's start before it is.  The value buffers are zeroed once, before
    the first copy: a ring that holds fewer positions leaves rows that no
    copy of the call has reached, and a masked position contributes 0 x
    row."""
    @pl.when(w == 0)
    def _():
        values[...] = jnp.zeros(values.shape, values.dtype)
        chunk_dma(0, 0, 0, "start")

    buf = w % 2
    chunk_dma(w, 0, buf, "wait")
    pl.when(w + 1 < n_slots)(lambda: chunk_dma(w + 1, 0, 1 - buf, "start"))
    return attend(buf, width, carry)


def _softmax_step(s, rem, values, carry, round_probs=False, heads=None):
    """One update of the online softmax: scores ``s (rows, T)`` float32,
    ``values (T, C)`` as stored; statistics and accumulator float32.
    ``rem``: positions from ``rem`` on are masked (``None``: all are
    visible).  ``round_probs``: the probabilities meet the values in the
    values' own dtype (one matmul, as the flash kernels do) and not as
    exact float32 terms.  ``heads``: ``values`` holds that many heads
    side by side and row group ``g`` reads head ``g``: the accumulator
    is ``(rows, C / heads)``, the product's diagonal blocks."""
    acc, m, l = carry
    if rem is not None:
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < rem, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
    if round_probs:
        p = p.astype(values.dtype)
    acc = acc * corr
    pv = _dot_f32(p, values, (((1,), (0,)), ((), ())))
    if heads is not None:
        g, c = pv.shape[0] // heads, values.shape[1] // heads
        pv = jnp.concatenate([pv[i * g:(i + 1) * g, i * c:(i + 1) * c]
                              for i in range(heads)], axis=0)
    return acc + pv, m_new, l_new


def _kernel(layer_ref, tables_ref, lens_ref,            # scalar prefetch
            q_ref, kc_ref, vc_ref, *refs,   # inputs (a sink), output, scratch
            n_head, n_kv_head, head_dim, v_head_dim, rows, scale, pages,
            table_width, block_size, window=None, sink=False):
    """``window`` None: the slot's block table, positions ``[0,
    seq_len)``.  Else the table is the slot's ring (``rlt_ring_decode``):
    location ``u`` holds position ``pos - ((pos - u) mod R*Bs)``, visible
    iff that lies in ``(pos - window, pos)`` and is not negative.
    ``sink``: an input ``(rows, 1)`` float32 after the current V row,
    one logit a query head that no value follows."""
    if sink:
        sink_ref, *refs = refs
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, marks = refs
    w = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_vis, n_pages, chunk_dma = _table_walk(
        tables_ref, lens_ref, layer_ref[0],
        ((k_hbm, kbuf), (v_hbm, vbuf)), sems, pages=pages,
        table_width=table_width, block_size=block_size)
    if window is None:
        _open_walk(w, marks, chunk_dma)

    group = n_head // n_kv_head

    def heads_of(width):
        """``(rows, Hkv*width)``: True where row h meets the columns of
        the K/V head it reads, heads ``width`` columns apart."""
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, n_kv_head * width), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, n_kv_head * width), 1)
        kv = row if group == 1 else row // group
        return (col >= kv * width) & (col < (kv + 1) * width)

    hv = n_kv_head * v_head_dim         # the V pool's row
    diag = heads_of(head_dim)           # on the K pool's row
    if group == 1:
        q_rows = q_ref[0]               # (1, hd): every row the same
    else:
        # Query head h scores K/V head h // group: row h holds its
        # query in that head's columns, zeros elsewhere.
        q_rows = jnp.concatenate([q_ref[0]] * n_kv_head, axis=1)
    q32 = jnp.where(diag, q_rows.astype(jnp.float32), 0.0)  # (rows, hd)
    q_bd = q32.astype(q_ref.dtype)  # exact: a cast back to q's own dtype

    # The current token is position seq_len: it opens the running
    # softmax (max = its score, sum = 1, accumulator = its V row).
    m0 = jnp.sum(q32 * kc_ref[0].astype(jnp.float32), axis=1,
                 keepdims=True) * scale
    l0 = jnp.ones((rows, 1), jnp.float32)
    # A ring is one step and grouped (``ring_decode_tiles``): its heads
    # take their values off the product's diagonal blocks there, so the
    # accumulator is one value head wide, ``(rows, Dv)`` (rows are the
    # heads: ``_heads_tile``).
    heads = None if window is None else n_kv_head
    if heads is None:
        acc0 = jnp.broadcast_to(vc_ref[0].astype(jnp.float32), (rows, hv))
    else:
        acc0 = jnp.concatenate([jnp.broadcast_to(
            vc_ref[0, :, g * v_head_dim:(g + 1) * v_head_dim].astype(
                jnp.float32), (group, v_head_dim))
            for g in range(n_kv_head)], axis=0)

    def attend(buf, width, carry, rem=None):
        at = (buf, pl.ds(0, width))
        s = _dot_f32(q_bd, kbuf[at], (((1,), (1,)), ((), ()))) * scale
        if window is not None:
            # How far back of pos location u's position lies, in [0,
            # ring): 0 is the location the current token takes, which
            # still holds the position it evicts; before the ring first
            # wraps, u >= pos lies more than pos back.  The ring mask
            # hides every location the clamped walk left uncopied.
            ring = table_width * block_size
            pos = lens_ref[w]
            back = pos % ring - jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            back = jnp.where(back < 0, back + ring, back)
            s = jnp.where((back > 0) & (back < window) & (back <= pos), s,
                          _NEG_INF)
        return _softmax_step(s, rem, vbuf[at], carry, heads=heads)

    # A key row's score is masked by a select whatever the row holds:
    # only the values need finite rows.
    if window is None:
        acc, m, l = _walk_chunks(
            w, n_slots, n_vis(w), n_pages(w), chunk_dma, marks, attend,
            (vbuf,), (acc0, m0, l0), pages=pages, block_size=block_size)
    else:
        acc, m, l = _ring_walk(w, n_slots, chunk_dma, vbuf, attend,
                               (acc0, m0, l0), width=pages * block_size)
    if sink:
        # The sink is one more key, with no value: it joins the
        # statistics once, after every position.
        b = sink_ref[...]
        m_new = jnp.maximum(m, b)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.exp(b - m_new)
        acc = acc * corr
    if heads is not None:
        o_ref[0] = acc / l
        return
    vdiag = diag if v_head_dim == head_dim else heads_of(v_head_dim)
    out = jnp.where(vdiag, acc / l, 0.0)
    if group == 1:
        o_ref[0] = jnp.sum(out, axis=0, keepdims=True)
    else:
        # Row h's output lies in its K/V head's value columns: fold the
        # column groups onto one head's value width.
        o_ref[0] = functools.reduce(
            jnp.add, [out[:, g * v_head_dim:(g + 1) * v_head_dim]
                      for g in range(n_kv_head)])


def paged_decode_attention(
    q: jax.Array,
    k_cur: jax.Array,
    v_cur: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    *,
    n_head: int,
    scale: float,
    n_kv_head: Optional[int] = None,
) -> jax.Array:
    """Attention of one new token per slot over its paged cache.

    With ``n_kv_head`` fewer than ``n_head`` (grouped queries) the
    pools' rows are ``Hkv*Dh`` (K) and ``Hkv*Dv`` (V) wide and query
    head ``h`` attends K/V head ``h // (H / Hkv)``; the value width
    ``Dv`` is the V pool's own (``Dh`` where the two pools' rows are
    alike).  ``None`` is one K/V head per query head, the program it
    always was.

    Args:
        q: ``(W, H*Dh)`` queries (any float dtype).
        k_cur, v_cur: ``(W, Hkv*Dh)``, ``(W, Hkv*Dv)`` the new token's
            own K and V rows in the pools' dtype (what the caller will
            store at position ``seq_lens[w]``); the pool is not read
            there.
        k_pool, v_pool: ``(L, N, Bs, Hkv*Dh)``, ``(L, N, Bs, Hkv*Dv)``,
            read at ``layer`` only.
        layer: scalar int32 layer index.
        block_tables: ``(W, M)`` int32 physical block ids.
        seq_lens: ``(W,)`` int32 cache positions already in the pool per
            slot; positions ``[0, seq_lens[w])`` of the pool are visible
            (clamped to the table's ``M * Bs``).

    Returns:
        ``(W, H*Dv)`` float32, head ``h`` in columns ``[h*Dv, (h+1)*Dv)``.
    """
    n_kv_head = n_head if n_kv_head is None else n_kv_head
    if not _heads_tile(k_pool, v_pool, n_head, n_kv_head, q.shape[1]):
        raise ValueError(
            f"rlt_paged_decode does not tile a {k_pool.dtype} pool of "
            f"shape {k_pool.shape} (values {v_pool.shape}) for {n_head} "
            f"query heads of {q.shape[1] // n_head} on {n_kv_head} K/V "
            f"heads: take the XLA path (attn_impl='xla' or 'auto')"
        )
    M, Bs = block_tables.shape[1], k_pool.shape[2]
    return _decode_call(
        q, k_cur, v_cur, k_pool, v_pool, layer, block_tables, seq_lens,
        n_head=n_head, n_kv_head=n_kv_head, scale=scale,
        pages=_pages_per_chunk(M, Bs, CHUNK_POSITIONS))


def _heads_tile(k_pool, v_pool, n_head: int, n_kv_head: int,
                q_width: int) -> bool:
    """What the grouped kernel body tiles: pools :func:`paged_decode_tiles`
    takes, rows of ``n_kv_head`` heads; grouped, the output folds value
    columns one head wide, so whole lanes, and the heads whole bf16
    sublane tiles; one K/V head a query head, alike rows."""
    if not (paged_decode_tiles(k_pool) and paged_decode_tiles(v_pool)):
        return False
    hd, hv = k_pool.shape[3], v_pool.shape[3]
    head_dim, v_head_dim = q_width // n_head, hv // n_kv_head
    grouped = n_kv_head != n_head
    return (hd == n_kv_head * head_dim and hv == n_kv_head * v_head_dim
            and not n_head % n_kv_head
            and not (grouped and (v_head_dim % 128 or n_head % 16))
            and (grouped or hv == hd))


def _decode_call(q, k_cur, v_cur, k_pool, v_pool, layer, block_tables,
                 seq_lens, *, n_head, n_kv_head, scale, pages, window=None,
                 sink=None):
    """The pallas call of :func:`_kernel` (shapes as
    :func:`paged_decode_attention` takes them, checked by the caller):
    ``rlt_paged_decode``, or with a ``window`` (and a ``sink``) as
    :func:`ring_decode_attention` ``rlt_ring_decode``."""
    W, qd = q.shape
    _, _, Bs, hd = k_pool.shape
    hv = v_pool.shape[3]
    M = block_tables.shape[1]
    P = pages
    head_dim = qd // n_head
    v_head_dim = hv // n_kv_head
    grouped = n_kv_head != n_head
    rows = -(-n_head // 16) * 16  # whole bf16 sublane tiles, _split3 stacks
    kernel = functools.partial(
        _kernel, n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        v_head_dim=v_head_dim, rows=rows, scale=scale, pages=P,
        table_width=M, block_size=Bs, window=window, sink=sink is not None,
    )
    row_spec = pl.BlockSpec((1, 1, hd), lambda w, *_: (w, 0, 0))
    v_row_spec = pl.BlockSpec((1, 1, hv), lambda w, *_: (w, 0, 0))
    # Grouped queries arrive one head a row, (W, H, Dh), and leave so
    # at the value width, (W, H, Dv).
    q_spec = pl.BlockSpec((1, n_head, head_dim), lambda w, *_: (w, 0, 0)) \
        if grouped else row_spec
    o_spec = pl.BlockSpec((1, n_head, v_head_dim), lambda w, *_: (w, 0, 0)) \
        if grouped else row_spec
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1),
                block_tables.astype(jnp.int32).reshape(-1),
                seq_lens.astype(jnp.int32))
    inputs = [q.reshape(W, n_head, head_dim) if grouped
              else q.reshape(W, 1, hd),
              k_cur.reshape(W, 1, hd), v_cur.reshape(W, 1, hv)]
    in_specs = [q_spec, row_spec, v_row_spec]
    if sink is not None:
        # One logit a query head, a row each (the padding rows' are 0).
        inputs.append(jnp.pad(sink.astype(jnp.float32),
                              (0, rows - n_head)).reshape(rows, 1))
        in_specs.append(pl.BlockSpec((rows, 1), lambda w, *_: (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(W,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=o_spec,
        scratch_shapes=[
            pltpu.VMEM((2, P * Bs, hd), k_pool.dtype),
            pltpu.VMEM((2, P * Bs, hv), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((3,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (W, n_head, v_head_dim) if grouped else (W, 1, hd), jnp.float32),
        # Slots run in order: each step leaves the next slot's first
        # chunk in flight and the walk's marks in SMEM.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(
                2 * P * Bs * (hd * k_pool.dtype.itemsize
                              + hv * v_pool.dtype.itemsize)),
        ),
        interpret=_interpret(),
        name="rlt_paged_decode" if window is None else "rlt_ring_decode",
    )(*prefetch, *inputs, k_pool, v_pool)
    return out.reshape(W, n_head * v_head_dim)


# ---------------------------------------------------------------------------
# a window layer's decode: the slot's ring, read whole
# ---------------------------------------------------------------------------

def ring_decode_tiles(k_pool, v_pool, n_head: int, n_kv_head: int) -> bool:
    """What ``rlt_ring_decode`` tiles: grouped heads (several query heads
    a K/V head) on the rows :func:`_heads_tile` takes (a query as wide as
    a key head)."""
    return (n_kv_head != n_head and k_pool.ndim == 4
            and not k_pool.shape[3] % n_kv_head
            and _heads_tile(k_pool, v_pool, n_head, n_kv_head,
                            n_head * (k_pool.shape[3] // n_kv_head)))


def ring_decode_supported(k_pool, v_pool, n_head: int,
                          n_kv_head: int) -> bool:
    """Whether ``attn_impl="auto"`` takes ``rlt_ring_decode`` for a window
    layer's pools (as :func:`paged_decode_supported`: backend, shapes,
    the ``paged`` kernel family's switch)."""
    return (not kernel_family_disabled("paged")
            and jax.default_backend() == "tpu"
            and ring_decode_tiles(k_pool, v_pool, n_head, n_kv_head))


def ring_decode_attention(
    q: jax.Array,
    k_cur: jax.Array,
    v_cur: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer: jax.Array,
    ring_tables: jax.Array,
    seq_lens: jax.Array,
    *,
    n_head: int,
    n_kv_head: int,
    scale: float,
    window: int,
    sink: Optional[jax.Array] = None,
) -> jax.Array:
    """A window layer's attention of one new token per slot over its
    ring, read where it lies in the ring pools.

    A slot's ring is ``R`` blocks of ``Bs`` positions: position ``p``
    lives at location ``p mod R*Bs`` (block ``ring_tables[w, (p // Bs)
    mod R]``), so the ring holds the last ``R*Bs`` positions.  The
    current token, position ``seq_lens[w]``, sees the ``window - 1``
    before it and itself; its own rows arrive as operands (the location
    it will take still holds the position it evicts).  Shapes, heads and
    arithmetic are :func:`paged_decode_attention`'s; ``sink`` ``(H,)``
    float32 is each query head's logit of a key with no value
    (``exp(s_j) / (exp(b) + sum_j exp(s_j))``).  A ring is one step of
    the walk: ``R*Bs`` positions a slot, copied while the slot before is
    computed.

    Returns:
        ``(W, H*Dv)`` float32, head ``h`` in columns ``[h*Dv, (h+1)*Dv)``.
    """
    if not ring_decode_tiles(k_pool, v_pool, n_head, n_kv_head) or (
            q.shape[1] != n_head * (k_pool.shape[3] // n_kv_head)):
        raise ValueError(
            f"rlt_ring_decode does not tile a {k_pool.dtype} ring pool of "
            f"shape {k_pool.shape} (values {v_pool.shape}) for {n_head} "
            f"query heads on {n_kv_head} K/V heads: take the XLA path "
            f"(attn_impl='xla' or 'auto')"
        )
    return _decode_call(
        q, k_cur, v_cur, k_pool, v_pool, layer, ring_tables, seq_lens,
        n_head=n_head, n_kv_head=n_kv_head, scale=scale,
        pages=ring_tables.shape[1], window=window, sink=sink)


# ---------------------------------------------------------------------------
# latent (MLA) decode: one shared row a position, absorbed form
# ---------------------------------------------------------------------------

def _mla_kernel(layer_ref, tables_ref, lens_ref,        # scalar prefetch
                q_ref, cur_ref, pool_hbm,               # inputs
                o_ref,                                  # output
                buf, sems, marks,                       # scratch
                *, n_head, rank, scale, pages, table_width, block_size):
    w = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_vis, n_pages, chunk_dma = _table_walk(
        tables_ref, lens_ref, layer_ref[0], ((pool_hbm, buf),), sems,
        pages=pages, table_width=table_width, block_size=block_size)
    _open_walk(w, marks, chunk_dma)

    q = q_ref[0]                                        # (H, row)
    q32 = q.astype(jnp.float32)
    cur = cur_ref[0].astype(jnp.float32)                # (1, row)
    # The current token's own row is position seq_len and opens the
    # running softmax; its first ``rank`` columns are its value.
    m0 = jnp.sum(q32 * cur, axis=1, keepdims=True) * scale
    l0 = jnp.ones((n_head, 1), jnp.float32)
    acc0 = jnp.broadcast_to(cur[:, :rank], (n_head, rank))

    def attend(b, width, carry, rem=None):
        # One tile, read once from HBM: every column is a key column
        # (the padding lanes are zero in q), the first ``rank`` are the
        # values.  All heads share it: a matmul of H rows.
        at = (b, pl.ds(0, width))
        s = _dot_f32(q, buf[at], (((1,), (1,)), ((), ()))) * scale
        # The probabilities meet the values in the pool's dtype, as in
        # the prefill's flash kernel: one matmul of H rows, not three.
        return _softmax_step(s, rem, buf[at + (pl.ds(0, rank),)], carry,
                             round_probs=True)

    acc, _, l = _walk_chunks(
        w, n_slots, n_vis(w), n_pages(w), chunk_dma, marks, attend, (buf,),
        (acc0, m0, l0), pages=pages, block_size=block_size)
    o_ref[0] = acc / l


def mla_decode_tiles(pool: jax.Array, n_head: int, rank: int) -> bool:
    """What ``rlt_mla_decode`` tiles: a pool :func:`paged_decode_tiles`
    takes, the value columns whole lanes of it, the heads whole sublane
    tiles."""
    return (paged_decode_tiles(pool) and rank % 128 == 0
            and rank <= pool.shape[3]
            and n_head % _sublane(jnp.bfloat16) == 0)


def mla_decode_supported(pool: jax.Array, n_head: int, rank: int) -> bool:
    """Whether ``attn_impl="auto"`` takes ``rlt_mla_decode`` (as
    :func:`paged_decode_supported`: backend, shapes, the ``paged``
    kernel family's switch)."""
    return (not kernel_family_disabled("paged")
            and jax.default_backend() == "tpu"
            and mla_decode_tiles(pool, n_head, rank))


def mla_decode_attention(
    q: jax.Array,
    row_cur: jax.Array,
    pool: jax.Array,
    layer: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    *,
    rank: int,
    scale: float,
    impl: str = "pallas",
) -> jax.Array:
    """Latent attention of one new token per slot, absorbed form.

    Every cached position of a layer is ONE row ``[c | k_r | 0]`` shared
    by all heads: the compressed latent (``rank`` columns, keys and
    values both), the rotary key, lanes of zero padding.  A head's
    query is ``[q_n W_uk | q_r | 0]``, its score the dot product with
    the row, its output ``P @ c`` (the caller applies ``W_uv``).

    Args:
        q: ``(W, H, row)`` absorbed queries, zero in the padding lanes.
        row_cur: ``(W, row)`` the new token's own row in the pool's
            dtype (what the caller will store at ``seq_lens[w]``).
        pool: ``(L, N, Bs, row)``, read at ``layer`` only.
        block_tables: ``(W, M)`` int32; seq_lens ``(W,)`` positions
            already in the pool (0 = an idle slot: its own row alone).
        impl: ``"pallas"`` (the ``rlt_mla_decode`` kernel: resident
            blocks only, each tile read once for scores and values) or
            ``"xla"`` (the whole table gathered and masked; the same
            arithmetic: scores' products accumulated in float32, softmax
            statistics in float32, the probabilities rounded to the
            pool's dtype where they meet the values, their products
            accumulated in float32).

    Returns:
        ``(W, H, rank)`` float32.
    """
    W, H, row = q.shape
    _, _, Bs, _ = pool.shape
    M = block_tables.shape[1]
    lens = jnp.minimum(seq_lens.astype(jnp.int32), M * Bs)
    if impl == "xla":
        ctx = pool[layer][block_tables].reshape(W, M * Bs, row)
        ctx = jnp.concatenate([ctx, row_cur[:, None]], axis=1).astype(
            jnp.float32)
        s = jnp.einsum("whc,wsc->whs", q.astype(jnp.float32), ctx,
                       precision=jax.lax.Precision.HIGHEST) * scale
        vis = jnp.arange(M * Bs + 1)[None, :] < lens[:, None]
        vis = vis.at[:, -1].set(True)
        probs = jax.nn.softmax(
            jnp.where(vis[:, None, :], s, _NEG_INF), axis=-1)
        probs = probs.astype(pool.dtype).astype(jnp.float32)
        return jnp.einsum("whs,wsr->whr", probs, ctx[..., :rank],
                          precision=jax.lax.Precision.HIGHEST)
    if impl != "pallas":
        raise ValueError(f"Unknown latent decode impl {impl!r} (xla|pallas)")
    if pool.shape[3] != row or not mla_decode_tiles(pool, H, rank):
        raise ValueError(
            f"rlt_mla_decode does not tile a {pool.dtype} pool of shape "
            f"{pool.shape} for {H} heads on rows of {row} with {rank} "
            f"value columns: take the XLA path (attn_impl='xla' or 'auto')"
        )
    P = _pages_per_chunk(M, Bs, CHUNK_POSITIONS)
    kernel = functools.partial(
        _mla_kernel, n_head=H, rank=rank, scale=scale, pages=P,
        table_width=M, block_size=Bs,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(W,),
        in_specs=[
            pl.BlockSpec((1, H, row), lambda w, *_: (w, 0, 0)),
            pl.BlockSpec((1, 1, row), lambda w, *_: (w, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda w, *_: (w, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P * Bs, row), pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((3,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, H, rank), jnp.float32),
        # Slots run in order, as in rlt_paged_decode.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(
                2 * P * Bs * row * pool.dtype.itemsize),
        ),
        interpret=_interpret(),
        name="rlt_mla_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32).reshape(-1),
        lens,
        q,
        row_cur.reshape(W, 1, row),
        pool,
    )
