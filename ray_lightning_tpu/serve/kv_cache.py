"""Paged/blocked KV cache: one block pool shared by every sequence.

``models/generate.py`` allocates ONE contiguous ``(L, B, total, H, Dh)``
cache per batch — fine for a fixed batch generating in lockstep, fatal
for serving: every request would own ``total_len`` slots for its whole
lifetime, and a new request could not join until the whole batch
finished.  The serving cache is instead a pool of fixed-size token
blocks (the vLLM/PagedAttention layout, TPU-shaped):

* **pool** — ``k``/``v`` each ``(L, num_blocks, block_size, H*Dh)``:
  a position's heads lie side by side in one row, so a block of one
  layer is one contiguous, tile-dense ``(block_size, H*Dh)`` slab (a
  minor ``(H, Dh)`` pair would pad every bf16 tile of (16, 128)).
  One allocation for the whole server, sized by memory, not by batch;
* **block tables** — per-slot ``(max_blocks_per_seq,)`` int32 rows
  mapping a sequence's logical block index → physical pool block.
  Tables live host-side (numpy, mutated by the scheduler between steps)
  and ride into the compiled step as ordinary int32 operands — shapes
  never change, so steady-state serving never recompiles;
* **allocator** — a host-side free list.  Finished/evicted requests
  free their blocks immediately; the next admission reuses them.

Physical block 0 is reserved as the **trash block**: inactive slots
point their writes at it, so the fixed-width decode program needs no
active-mask branch — garbage lands where nothing ever reads.

Device programs (pure functions, jitted by the engine):

* :func:`paged_prefill` — one padded prompt bucket through the SAME
  stacked-layer block scan the static path uses
  (``generate._trunk_blocks``), then the per-layer k/v scattered into
  the sequence's pool blocks.  Compiled once per bucket length;
* :func:`paged_decode_step` — one token for EVERY slot, attended over
  positions ``[0, seq_len]``.  ONE fixed-width program for the server's
  lifetime, in one of two forms chosen from the backend, the pool's
  shape and its dtype (``attn_impl="auto"``): on TPU the pool stays
  where it is — the ``rlt_paged_decode`` kernel
  (``ops/paged_attention.py``) reads only the blocks ``seq_lens`` says
  are resident, straight from the pool, takes the new token's own k/v
  as operands, and all layers' new rows are scattered into the donated
  pool once after the layer loop; elsewhere (CPU, shapes the kernel
  does not tile) the XLA path scatters the new k/v per layer, gathers
  each slot's whole table and masks.

Numerics match the contiguous path by construction: both forms lay a
sequence's blocks back into logical order, hide exactly the slots the
static path's causal mask hides, and keep scores/softmax/PV in f32
(see ``generate._block_pass``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.generate import (
    _embed, _head_logits, _trunk_blocks,
)
from ray_lightning_tpu.models.gpt import (
    GPTConfig, _layer_norm, _mlp_residual, _moe_residual,
)
from ray_lightning_tpu.models.quant import resolve_weight
from ray_lightning_tpu.ops.attention import _NEG_INF
from ray_lightning_tpu.ops.lora import apply_lora

__all__ = [
    "BlockAllocator",
    "PagedKVCache",
    "PrefixIndex",
    "paged_prefill",
    "paged_decode_step",
    "paged_verify_step",
    "sample_tokens",
    "make_slot_keys",
    "extend_block_coverage",
    "truncate_to",
    "import_blocks",
    "copy_blocks",
]

# Physical block 0 is never allocated: it is the write target for
# inactive slots (and the padding entry of short block tables), so the
# decode program stays branch-free.
TRASH_BLOCK = 0


class BlockAllocator:
    """Host-side free list over the physical block pool, with per-block
    reference counts.

    jax-free and O(1) per op.  Double-free and foreign-id frees raise —
    a scheduler bug that silently re-issued a live block would corrupt
    another request's cache, the one failure mode a serving cache must
    never shrug off.

    Refcounts are the sharing substrate of the prefix cache: a freshly
    allocated block carries one reference (its owning chain);
    :meth:`retain` hands the SAME physical block to another holder
    (another request's block table, or the resident
    :class:`PrefixIndex`), and :meth:`free` becomes decrement-release —
    the block returns to the free list only when its LAST holder drops
    it.  Every holder frees through the same call, so no caller needs
    to know whether it was the last one.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block {TRASH_BLOCK} is "
                f"reserved), got {num_blocks}"
            )
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed blocks are re-issued first
        # (their pool pages are the warmest).
        self._free: List[int] = list(range(num_blocks - 1, TRASH_BLOCK, -1))
        self._refs: Dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return len(self._refs)

    def refcount(self, b: int) -> int:
        """Holders of physical block ``b`` (0 = not live)."""
        return self._refs.get(b, 0)

    def is_shared(self, b: int) -> bool:
        """True when more than one holder references ``b`` — the block
        is read-only to every holder until copy-on-write or release."""
        return self._refs.get(b, 0) > 1

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` physical block ids, or ``None`` (all-or-nothing) when
        the pool cannot cover the request."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._refs[b] = 1
        return ids

    def retain(self, ids) -> None:
        """Bump the refcount of live blocks ``ids`` — the claim half of
        prefix sharing (zero device work: the new holder just points
        its block table at the same physical blocks)."""
        for b in ids:
            if b not in self._refs:
                raise RuntimeError(
                    f"retain of block {b} which is not live — a chain "
                    f"cannot share blocks nobody owns"
                )
        for b in ids:
            self._refs[b] += 1

    def free(self, ids) -> None:
        for b in ids:
            if b not in self._refs:
                raise RuntimeError(
                    f"free of block {b} which is not live (double-free "
                    f"or foreign id) — scheduler bookkeeping bug"
                )
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)


def extend_block_coverage(
    allocator: BlockAllocator,
    blocks: List[int],
    table_row,
    upto_pos: int,
    block_size: int,
) -> bool:
    """Grow ``blocks``/``table_row`` until cache position ``upto_pos``
    is writable.  All-or-nothing: either every missing block is
    allocated (True) or none are (False = pool dry) — a partially
    covered multi-token write would scatter past its allocation.

    The multi-token append primitive of the speculative-decoding path:
    a verify step writes K+1 positions in one dispatch, so coverage is
    claimed for the whole window BEFORE the dispatch, and
    :func:`truncate_to` returns the rejected tail's blocks afterwards.
    """
    need = (upto_pos // block_size) + 1 - len(blocks)
    if need <= 0:
        return True
    ids = allocator.alloc(need)
    if ids is None:
        return False
    start = len(blocks)
    blocks.extend(ids)
    table_row[start: start + len(ids)] = ids
    return True


def truncate_to(
    allocator: BlockAllocator,
    blocks: List[int],
    table_row,
    n_tokens: int,
    block_size: int,
) -> int:
    """Shrink a sequence's block coverage to exactly ``n_tokens`` cache
    slots: blocks past the covering prefix are freed back to the pool
    and their table entries restored to the trash block.  Returns the
    number of blocks freed.

    Pure ``seq_lens``/allocator arithmetic — the rollback half of a
    speculative verify tick (rejected drafts' cache slots are garbage
    the visibility mask already hides; this returns their BLOCKS).
    """
    keep = -(-n_tokens // block_size) if n_tokens > 0 else 0
    freed = blocks[keep:]
    if not freed:
        return 0
    del blocks[keep:]
    allocator.free(freed)
    table_row[keep: keep + len(freed)] = TRASH_BLOCK
    return len(freed)


class PagedKVCache:
    """The device block pool + its allocator.

    ``pool`` is a ``{"k", "v"}`` dict of ``(L, N, Bs, H*Dh)`` arrays —
    the same stacked-layer leading axis as the static cache, indexed
    ``[layer, block, offset]``; a row holds the position's heads side
    by side (head ``h`` in columns ``[h*Dh, (h+1)*Dh)``).  The engine owns the authoritative pool arrays
    (they flow through the donated compiled steps); this object carries
    the geometry and the allocator.
    """

    def __init__(self, cfg: GPTConfig, num_blocks: int, block_size: int,
                 dtype=jnp.float32):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.cfg = cfg
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.dtype = dtype
        self.allocator = BlockAllocator(num_blocks)

    def init_pool(self) -> Dict[str, jax.Array]:
        cfg = self.cfg
        shape = (cfg.n_layer, self.num_blocks, self.block_size,
                 cfg.n_head * cfg.head_dim)
        return {"k": jnp.zeros(shape, self.dtype),
                "v": jnp.zeros(shape, self.dtype)}

    def blocks_for(self, n_tokens: int) -> int:
        """Physical blocks needed to hold ``n_tokens`` cache slots."""
        return -(-n_tokens // self.block_size)

    def export_blocks(self, pool: Dict[str, jax.Array],
                      block_ids) -> Dict[str, Any]:
        """Gather ``block_ids``'s k/v content to HOST numpy — the
        producer half of a disaggregated KV handoff.

        A prefill worker prefills into its OWN pool blocks, exports
        them here, frees the blocks, and ships the payload over the
        queue plane; the consuming decode replica scatters it into
        whatever free blocks ITS allocator hands out
        (:func:`import_blocks`) — physical ids never cross the wire,
        only logical block content, so producer and consumer pools
        need not agree on anything but geometry.  The payload is
        ``(L, n, Bs, H, Dh)`` per tensor whatever the pool's own row
        layout.
        """
        import numpy as np

        ids = np.asarray(list(block_ids), np.int32)
        if ids.size and (ids.min() <= TRASH_BLOCK
                         or ids.max() >= self.num_blocks):
            raise ValueError(
                f"export_blocks: ids outside (trash, {self.num_blocks})"
            )
        cfg = self.cfg
        wire = (cfg.n_layer, ids.size, self.block_size, cfg.n_head,
                cfg.head_dim)
        return {key: np.asarray(pool[key][:, ids]).reshape(wire)
                for key in ("k", "v")}


def import_blocks(
    pool: Dict[str, jax.Array],
    payload: Dict[str, jax.Array],
    block_ids: jax.Array,
) -> Dict[str, jax.Array]:
    """Scatter an exported KV payload into ``block_ids`` of ``pool`` —
    the consumer half of a disaggregated handoff (jittable; the engine
    compiles one executable per bucket block count, exactly like the
    bucketed prefill set, so steady-state imports never recompile).

    The payload is the wire's ``(L, n, Bs, H, Dh)`` per tensor
    (:meth:`PagedKVCache.export_blocks`); the heads are folded into the
    pool's rows here.  ``block_ids`` come from the CONSUMER's allocator
    (never the trash
    block — the allocator cannot issue it), and the caller rewrites the
    slot's block table to these ids, so every trash-block invariant of
    the decode/verify programs is preserved by construction.
    """
    return {
        key: pool[key].at[:, block_ids].set(
            payload[key].astype(pool[key].dtype).reshape(
                payload[key].shape[:3] + (-1,)
            )
        )
        for key in ("k", "v")
    }


def copy_blocks(
    pool: Dict[str, jax.Array],
    src_ids: jax.Array,
    dst_ids: jax.Array,
) -> Dict[str, jax.Array]:
    """Copy the k/v content of ``src_ids`` into ``dst_ids`` — the
    copy-on-write primitive of the shared-block discipline (jittable;
    one fixed-width program per COW fan-out, compiled like the import
    set).

    A holder about to WRITE into a block whose refcount is > 1 must not
    (the other holders' caches would change under them): it allocates
    fresh blocks, copies the shared content here, swaps its block-table
    entries to the copies, and drops its references on the originals.
    The admission-time claim cap (the last prompt token is always
    recomputed, so every decode/verify/suffix write lands strictly past
    the shared frontier) means the serving plane never hits this in
    nominal flow — COW is the safety net that keeps the invariant
    locally checkable rather than globally assumed.
    """
    return {
        key: pool[key].at[:, dst_ids].set(pool[key][:, src_ids])
        for key in ("k", "v")
    }


class _ChainNode:
    """One radix-tree edge: a run of whole blocks with no branch."""

    __slots__ = ("keys", "ids", "children", "parent", "stamp")

    def __init__(self, keys, ids, parent, stamp):
        self.keys: List[Tuple[int, ...]] = keys   # per-block token tuples
        self.ids: List[int] = ids                 # physical block ids
        self.children: Dict[Tuple[int, ...], "_ChainNode"] = {}
        self.parent: Optional["_ChainNode"] = parent
        self.stamp = stamp


class PrefixIndex:
    """Radix tree of resident KV block chains, keyed by prompt tokens.

    The prefix cache of the serving plane: after a prompt is prefilled,
    its FULL blocks (every block whose ``block_size`` tokens were all
    written — the partial tail block keeps growing under decode and is
    never indexed) are inserted as a chain, and the index RETAINS a
    reference on each, so the chain stays resident after the request
    finishes.  A later request claims its longest whole-block shared
    prefix with :meth:`claim` — refcount bumps only, zero device work —
    and prefills just the uncovered suffix.

    Granularity is the block, deliberately: a physical block either
    holds exactly the claimed tokens' KV or it is not claimed, so
    sharing never needs sub-block copies, and the radix edges are runs
    of ``(tokens-per-block,)`` tuples compared whole.  Chains are keyed
    per ``key`` (the adapter name, or ``None`` for the base model),
    because adapter-bearing prefill writes adapter-specific KV — one
    tenant's chain must never satisfy another's lookup.

    Eviction (:meth:`evict`) walks least-recently-used LEAF edges and
    releases blocks tail-first, and ONLY blocks whose refcount is 1 —
    a block some live chain still holds is never evicted out from
    under it (releasing it would not free memory anyway; the holder's
    reference keeps it live).  Interior edges are pinned by their
    children: chain integrity means a prefix block never leaves before
    the blocks extending it.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.allocator = allocator
        self.block_size = block_size
        self._roots: Dict[Any, _ChainNode] = {}
        self._clock = 0
        self.cached_blocks = 0
        self.lookups = 0
        self.hits = 0
        self.blocks_claimed = 0
        self.blocks_inserted = 0
        self.blocks_evicted = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _block_keys(self, tokens) -> List[Tuple[int, ...]]:
        Bs = self.block_size
        n = len(tokens) // Bs
        return [tuple(int(t) for t in tokens[i * Bs:(i + 1) * Bs])
                for i in range(n)]

    def _match(self, key: Any, blocks: List[Tuple[int, ...]]) -> List[int]:
        root = self._roots.get(key)
        out: List[int] = []
        if root is None:
            return out
        node, i, stamp = root, 0, self._tick()
        while i < len(blocks):
            child = node.children.get(blocks[i])
            if child is None:
                break
            j = 0
            while (j < len(child.keys) and i < len(blocks)
                   and child.keys[j] == blocks[i]):
                out.append(child.ids[j])
                i += 1
                j += 1
            child.stamp = stamp
            if j < len(child.keys):
                break
            node = child
        return out

    def claim(self, key: Any, tokens, max_blocks: int) -> List[int]:
        """Longest resident whole-block prefix of ``tokens`` under
        ``key``, capped at ``max_blocks``, with a reference RETAINED on
        every returned block (the caller owns one free() per id, same
        as an alloc).  ``max_blocks`` is the caller's write-safety cap:
        the engine passes ``(prompt_len - 1) // block_size`` so the
        final prompt token is always recomputed (it produces the
        first-token logits) and every subsequent write lands strictly
        past the shared blocks."""
        self.lookups += 1
        if max_blocks <= 0:
            return []
        ids = self._match(key, self._block_keys(tokens))[:max_blocks]
        if not ids:
            return []
        self.allocator.retain(ids)
        self.hits += 1
        self.blocks_claimed += len(ids)
        return ids

    def insert(self, key: Any, tokens, block_ids) -> int:
        """Register ``tokens``'s full blocks (held in ``block_ids``, the
        owning chain's physical blocks in logical order) as a resident
        chain under ``key``.  Blocks already covered by an existing
        chain are skipped (the walk matches them by token content);
        newly stored blocks are RETAINED by the index.  Returns the
        number of blocks newly inserted."""
        blocks = self._block_keys(tokens)
        if len(block_ids) < len(blocks):
            raise ValueError(
                f"insert: {len(blocks)} full blocks of tokens but only "
                f"{len(block_ids)} block ids"
            )
        if not blocks:
            return 0
        root = self._roots.get(key)
        if root is None:
            root = self._roots[key] = _ChainNode([], [], None, 0)
        node, i, stamp, added = root, 0, self._tick(), 0
        while i < len(blocks):
            child = node.children.get(blocks[i])
            if child is None:
                keys = blocks[i:]
                ids = [int(b) for b in block_ids[i:len(blocks)]]
                self.allocator.retain(ids)
                new = _ChainNode(keys, ids, node, stamp)
                node.children[keys[0]] = new
                added += len(ids)
                break
            j = 0
            while (j < len(child.keys) and i < len(blocks)
                   and child.keys[j] == blocks[i]):
                i += 1
                j += 1
            child.stamp = stamp
            if j == len(child.keys):
                node = child
                continue
            if i == len(blocks):
                break  # strict prefix of an existing edge: fully covered
            # Diverged mid-edge: split the edge at j, then loop — the
            # next iteration hangs the new suffix under the split point.
            tail = _ChainNode(child.keys[j:], child.ids[j:], child,
                              child.stamp)
            tail.children = child.children
            for grand in tail.children.values():
                grand.parent = tail
            child.keys = child.keys[:j]
            child.ids = child.ids[:j]
            child.children = {tail.keys[0]: tail}
            node = child
        self.cached_blocks += added
        self.blocks_inserted += added
        return added

    def _leaves(self):
        out = []
        for root in self._roots.values():
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                if n.children:
                    stack.extend(n.children.values())
                else:
                    out.append(n)
        return out

    def evict(self, n_blocks: int) -> int:
        """Release up to ``n_blocks`` resident blocks, LRU leaves first,
        tail-first within a leaf, skipping any block a live chain still
        holds (refcount > 1).  Returns the number of blocks actually
        returned to the free list."""
        freed = 0
        visited: set = set()
        while freed < n_blocks:
            leaf = None
            for cand in self._leaves():
                if id(cand) in visited:
                    continue
                if leaf is None or cand.stamp < leaf.stamp:
                    leaf = cand
            if leaf is None:
                break
            visited.add(id(leaf))
            while leaf.keys and freed < n_blocks:
                b = leaf.ids[-1]
                if self.allocator.refcount(b) > 1:
                    break  # a live chain holds it: pinned
                leaf.keys.pop()
                leaf.ids.pop()
                self.allocator.free([b])
                self.cached_blocks -= 1
                self.blocks_evicted += 1
                freed += 1
            if not leaf.keys and leaf.parent is not None:
                leaf.parent.children = {
                    k: v for k, v in leaf.parent.children.items()
                    if v is not leaf
                }
        return freed

    def drop(self, key: Any) -> int:
        """Release every chain under ``key`` (adapter replaced/removed:
        its KV is stale the moment the factors change).  Blocks shared
        with in-flight chains stay live until those chains drop them."""
        root = self._roots.pop(key, None)
        if root is None:
            return 0
        dropped = 0
        stack = list(root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self.allocator.free(n.ids)
            dropped += len(n.ids)
        self.cached_blocks -= dropped
        return dropped

    def drop_all(self) -> int:
        """Release every resident chain (engine stop)."""
        return sum(self.drop(k) for k in list(self._roots))

    def stats(self) -> Dict[str, int]:
        return {
            "lookups": self.lookups,
            "hits": self.hits,
            "blocks_claimed": self.blocks_claimed,
            "blocks_inserted": self.blocks_inserted,
            "blocks_evicted": self.blocks_evicted,
            "cached_blocks": self.cached_blocks,
        }


def _heads(cfg: GPTConfig, z: jax.Array) -> jax.Array:
    """``(..., H*Dh)`` rows (the pool's, or a projection's) as
    ``(..., H, Dh)``."""
    return z.reshape(z.shape[:-1] + (cfg.n_head, cfg.head_dim))


def paged_prefill(
    cfg: GPTConfig,
    params: Dict[str, Any],
    pool: Dict[str, jax.Array],
    tokens: jax.Array,
    prompt_len: jax.Array,
    block_ids: jax.Array,
    compute_dtype=jnp.float32,
    adapters: Optional[Dict[str, jax.Array]] = None,
    adapter_id: Optional[jax.Array] = None,
    lora_impl: str = "xla",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One prompt through the full-sequence causal pass, cache written
    into the sequence's pool blocks.

    Args:
        tokens: ``(T,)`` int32, the prompt right-padded to a bucket
            length ``T`` that is a multiple of the pool's block size.
        prompt_len: scalar int32, the number of VALID leading tokens.
        block_ids: ``(T // block_size,)`` int32 physical blocks that
            will hold cache positions ``[0, T)`` of this sequence.

    Returns:
        ``(next-token logits (V,) f32 at position prompt_len - 1,
        updated pool)``.  Padding positions write garbage into the tail
        of the sequence's own blocks; decode masks ``s <= seq_len`` so
        it is never attended, and the sequence's own growth overwrites
        it slot by slot.

    Compiled once per bucket length ``T`` — the "few bucketed prompt
    lengths" prefill programs of the serving plane.

    ``adapters``/``adapter_id`` (multi-tenant LoRA): the pool's stacked
    per-layer factor buffers plus THIS prompt's scalar int32 slot id
    (an operand — any tenant rides the same bucket program; slot 0 is
    the zero-delta base model).  ``None`` keeps the graph
    byte-identical to pre-LoRA rounds.
    """
    c = compute_dtype
    T = tokens.shape[0]
    Bs = pool["k"].shape[2]
    if T % Bs != 0:
        raise ValueError(
            f"prefill bucket length {T} is not a multiple of the "
            f"block size {Bs}"
        )
    x = _embed(params, tokens[None], c) + params["wpe"][:T].astype(c)
    # The contiguous temp cache reuses the static path's stacked-layer
    # scan verbatim (ONE source for the block math), then the per-layer
    # k/v reshape into whole blocks and scatter into the pool.
    tmp = {
        "k": jnp.zeros((cfg.n_layer, 1, T, cfg.n_head, cfg.head_dim),
                       pool["k"].dtype),
        "v": jnp.zeros((cfg.n_layer, 1, T, cfg.n_head, cfg.head_dim),
                       pool["v"].dtype),
    }
    ad_ids = None if adapter_id is None else adapter_id.reshape((1,))
    hidden, tmp = _trunk_blocks(cfg, params, tmp, x, 0, c,
                                adapters=adapters, adapter_ids=ad_ids,
                                lora_impl=lora_impl)
    h_last = jax.lax.dynamic_index_in_dim(
        hidden[0], prompt_len - 1, axis=0, keepdims=False
    )
    logits = _head_logits(params, h_last, c)
    n = T // Bs
    out = {}
    for key in ("k", "v"):
        per_block = tmp[key][:, 0].reshape(
            cfg.n_layer, n, Bs, cfg.n_head * cfg.head_dim
        )
        out[key] = pool[key].at[:, block_ids].set(per_block)
    return logits, out


def paged_decode_step(
    cfg: GPTConfig,
    params: Dict[str, Any],
    pool: Dict[str, jax.Array],
    block_tables: jax.Array,
    seq_lens: jax.Array,
    tokens: jax.Array,
    compute_dtype=jnp.float32,
    write_limit: Optional[jax.Array] = None,
    adapters: Optional[Dict[str, jax.Array]] = None,
    adapter_ids: Optional[jax.Array] = None,
    lora_impl: str = "xla",
    attn_impl: str = "auto",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One token for every slot of the fixed-width active set.

    Args:
        block_tables: ``(W, M)`` int32 — each slot's physical blocks in
            logical order; unused entries (and whole inactive rows)
            point at the trash block.
        seq_lens: ``(W,)`` int32 — tokens already IN the cache per slot;
            the current token is written at this position.
        tokens: ``(W,)`` int32 — the token each slot feeds this step
            (inactive slots: anything; their row is masked by pointing
            at the trash block and never being read).
        write_limit: optional ``(W,)`` int32 — positions ``>= limit``
            write into the trash block instead of the slot's own blocks.
            The draft chain of the speculative path dispatches this
            program at positions past some slots' allocated coverage
            (uniform chain length over non-uniform per-slot widths);
            the limit redirects those strays (their logits are not
            used).  ``None`` = the plain serve decode program.
        adapters: optional stacked per-layer LoRA factor buffers
            (``serve/lora.py`` pool; leading axis L rides the scan)
            with per-slot ``adapter_ids`` int32 — each slot's own
            adapter delta lands on its qkv/proj projections (slot 0 =
            zero delta).  ``None`` = the pre-LoRA program.
        attn_impl: ``"auto"`` takes the ``rlt_paged_decode`` kernel
            where ``ops.paged_attention.paged_decode_supported`` says
            so (TPU, a pool it tiles) and the XLA gather elsewhere;
            ``"xla"`` / ``"pallas"`` name one (off TPU the kernel runs
            under the Pallas interpreter: tests).

    Returns:
        ``(logits (W, V) f32, updated pool)``.

    ONE compiled program for any mix of sequence lengths: the per-slot
    write position, the blocks read, and the visibility mask are all
    data, never shapes — join-on-arrival/evict-on-finish between steps
    only changes operand VALUES, so steady-state serving never
    recompiles.
    """
    from ray_lightning_tpu.ops.paged_attention import (
        paged_decode_attention, paged_decode_supported,
    )

    if attn_impl == "auto":
        attn_impl = "pallas" if paged_decode_supported(pool["k"]) else "xla"
    if attn_impl not in ("xla", "pallas"):
        raise ValueError(
            f"Unknown paged attention impl {attn_impl!r} (auto|xla|pallas)"
        )
    kernel = attn_impl == "pallas"
    c = compute_dtype
    Bs = pool["k"].shape[2]
    W, M = block_tables.shape
    S = M * Bs
    pos = seq_lens
    # Clamp the positional lookup: inactive slots carry pos 0, active
    # ones are scheduler-bounded to < seq_len; the clamp only guards
    # garbage from ever indexing out of the table.
    safe_pos = jnp.minimum(pos, params["wpe"].shape[0] - 1)
    x = _embed(params, tokens, c) + params["wpe"][safe_pos].astype(c)
    blk_idx = pos // Bs
    if write_limit is not None:
        # Chain positions may run past the table width; the clamp keeps
        # the lookup in bounds and the limit sends the write to trash.
        blk_idx = jnp.minimum(blk_idx, M - 1)
    write_blk = jnp.take_along_axis(
        block_tables, blk_idx[:, None], axis=1
    )[:, 0]
    if write_limit is not None:
        write_blk = jnp.where(pos < write_limit, write_blk, TRASH_BLOCK)
    write_off = pos % Bs
    scale = cfg.head_dim ** -0.5

    def gathered_attention(q, k_pool, v_pool):
        # Visible: cache positions [0, pos] inclusive — the current
        # token's own k/v count, exactly the static path's causal
        # frontier.  This form writes them before its gather; the
        # kernel takes them as operands beside the pool's [0, pos).
        visible = jnp.arange(S)[None, :] <= pos[:, None]
        ctx_k = _heads(cfg, k_pool[block_tables].reshape(W, S, cfg.d_model))
        ctx_v = _heads(cfg, v_pool[block_tables].reshape(W, S, cfg.d_model))
        scores = jnp.einsum(
            "whd,wshd->whs", _heads(cfg, q).astype(jnp.float32),
            ctx_k.astype(jnp.float32),
        ) * scale
        scores = jnp.where(visible[:, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum(
            "whs,wshd->whd", probs, ctx_v.astype(jnp.float32)
        ).reshape(W, cfg.d_model)

    def block(carry, layer):
        x, = carry
        # kv: the layer's own (N, Bs, H*Dh) slices of the pool (XLA),
        # or the layer's index into the whole pool (kernel).
        p, kv, ad = layer
        h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
        qkv = h @ resolve_weight(p, "qkv_w", c) + p["qkv_b"].astype(c)
        qkv = apply_lora(qkv, h, ad, "qkv", adapter_ids, lora_impl)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        k = k.astype(pool["k"].dtype)
        v = v.astype(pool["v"].dtype)
        if kernel:
            att = paged_decode_attention(
                q, k, v, pool["k"], pool["v"], kv, block_tables, pos,
                n_head=cfg.n_head, scale=scale,
            )
            out = (k, v)
        else:
            k_pool, v_pool = kv
            k_pool = k_pool.at[write_blk, write_off].set(k)
            v_pool = v_pool.at[write_blk, write_off].set(v)
            att = gathered_attention(q, k_pool, v_pool)
            out = (k_pool, v_pool)
        att = att.astype(c)
        proj = att @ resolve_weight(p, "proj_w", c) + p["proj_b"].astype(c)
        proj = apply_lora(proj, att, ad, "proj", adapter_ids, lora_impl)
        x = x + proj
        if cfg.n_experts > 0:
            # Same routed-MLP math as the static decode; the routed set
            # here is the W current tokens (see generate() caveat).
            x2, _ = _moe_residual(x[:, None], p, cfg, groups=1)
            x = x2[:, 0]
        else:
            x = _mlp_residual(x, p, c)
        return (x,), out

    if kernel:
        # The pool is closed over, read-only inside the loop: no layer
        # of it is sliced out or written back.  The L x W new rows go
        # into the (donated) pool in one scatter afterwards.
        kv = jnp.arange(cfg.n_layer, dtype=jnp.int32)
    else:
        kv = (pool["k"], pool["v"])
    (x,), (k_out, v_out) = jax.lax.scan(
        block, (x,), (params["blocks"], kv, adapters)
    )
    if kernel:
        # Every index explicit, one row a window: a slice over the
        # layer axis makes XLA re-lay the whole pool around the scatter.
        at = (kv[:, None], write_blk[None, :], write_off[None, :])
        k_out = pool["k"].at[at].set(k_out)
        v_out = pool["v"].at[at].set(v_out)
    logits = _head_logits(params, x, c)
    return logits, {"k": k_out, "v": v_out}


def paged_verify_step(
    cfg: GPTConfig,
    params: Dict[str, Any],
    pool: Dict[str, jax.Array],
    block_tables: jax.Array,
    seq_lens: jax.Array,
    tokens: jax.Array,
    write_limit: jax.Array,
    compute_dtype=jnp.float32,
    adapters: Optional[Dict[str, jax.Array]] = None,
    adapter_ids: Optional[jax.Array] = None,
    lora_impl: str = "xla",
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``T`` tokens for every slot in ONE dispatch — the target model's
    speculative verification program.  ``adapters``/``adapter_ids``
    apply each slot's own LoRA delta across its whole window (see
    :func:`paged_decode_step`); verification composes with the
    adapter pool because the TARGET is what carries the tenant's
    adapter — a base-model draft just proposes, and disagreements are
    corrected by the adapter-bearing verify sample.

    Where :func:`paged_decode_step` feeds one token per slot at
    ``seq_lens``, this feeds a ``(W, T)`` window — each slot's current
    token followed by its ``K = T - 1`` drafted tokens — at positions
    ``seq_lens + [0, T)``, writes all ``T`` k/v entries into the slot's
    blocks, and returns logits at EVERY window position, so the target
    scores K draft proposals at the cost of one (wider) dispatch
    instead of K sequential ones.  Causality within the window is the
    static path's frontier: query ``i`` sees cache positions
    ``<= seq_lens + i`` (its own fresh write included — the scatter
    lands before the gather, exactly like the decode step).

    Args:
        tokens: ``(W, T)`` int32 window per slot.  Slots speculating
            fewer than ``T - 1`` tokens pad with anything; their
            ``write_limit`` trashes the pad writes and the engine
            ignores the pad logits.
        write_limit: ``(W,)`` int32 — positions ``>= limit`` write into
            the trash block (inactive slots carry 0: every write
            trashed).

    Returns:
        ``(logits (W, T, V) f32, updated pool)``.

    Fixed ``(W, T)`` width for the engine's lifetime: accept/reject,
    rollback, and per-slot draft widths are all operand values, so the
    speculative steady state stays on the compiled-once program set.
    """
    c = compute_dtype
    Bs = pool["k"].shape[2]
    W, M = block_tables.shape
    T = tokens.shape[1]
    S = M * Bs
    pos = seq_lens[:, None] + jnp.arange(T)[None, :]          # (W, T)
    safe_pos = jnp.minimum(pos, params["wpe"].shape[0] - 1)
    x = _embed(params, tokens, c) + params["wpe"][safe_pos].astype(c)
    write_blk = jnp.take_along_axis(
        block_tables, jnp.minimum(pos // Bs, M - 1), axis=1
    )
    write_blk = jnp.where(pos < write_limit[:, None], write_blk,
                          TRASH_BLOCK)
    write_off = pos % Bs
    scale = cfg.head_dim ** -0.5
    visible = jnp.arange(S)[None, None, :] <= pos[:, :, None]  # (W, T, S)

    def block(carry, layer):
        x, = carry
        p, k_pool, v_pool, ad = layer  # pools (N, Bs, H*Dh) each
        h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
        qkv = h @ resolve_weight(p, "qkv_w", c) + p["qkv_b"].astype(c)
        qkv = apply_lora(qkv, h, ad, "qkv", adapter_ids, lora_impl)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        k_pool = k_pool.at[write_blk, write_off].set(
            k.astype(k_pool.dtype)
        )
        v_pool = v_pool.at[write_blk, write_off].set(
            v.astype(v_pool.dtype)
        )
        ctx_k = _heads(cfg, k_pool[block_tables].reshape(W, S, cfg.d_model))
        ctx_v = _heads(cfg, v_pool[block_tables].reshape(W, S, cfg.d_model))
        scores = jnp.einsum(
            "wthd,wshd->whts", _heads(cfg, q).astype(jnp.float32),
            ctx_k.astype(jnp.float32),
        ) * scale
        scores = jnp.where(visible[:, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        att = jnp.einsum(
            "whts,wshd->wthd", probs, ctx_v.astype(jnp.float32)
        ).reshape(W, T, cfg.d_model).astype(c)
        proj = att @ resolve_weight(p, "proj_w", c) + p["proj_b"].astype(c)
        proj = apply_lora(proj, att, ad, "proj", adapter_ids, lora_impl)
        x = x + proj
        if cfg.n_experts > 0:
            # Routed set = the W*T window tokens (see generate() caveat).
            x, _ = _moe_residual(x, p, cfg, groups=1)
        else:
            x = _mlp_residual(x, p, c)
        return (x,), (k_pool, v_pool)

    (x,), (k_new, v_new) = jax.lax.scan(
        block, (x,), (params["blocks"], pool["k"], pool["v"], adapters)
    )
    logits = _head_logits(params, x, c)
    return logits, {"k": k_new, "v": v_new}


class GPTServeFamily:
    """The seam :class:`~ray_lightning_tpu.serve.engine.ServeEngine`
    builds on: a model family's pool layout and its prefill and decode
    programs.  This is ``GPT``'s (one kind of cache state, the functions
    of this file); a module with a ``serve_family()`` method brings its
    own (``models/exaone_moe.py``: window and full layers in one cache
    manager), and the engine asks nothing else of a family:
    ``make_cache``, ``prefill``, ``decode``, ``prepare_params``,
    ``vocab_size``, ``two_kind`` (ring tables beside the block tables),
    ``refuses`` / ``refuses_why``, ``walk_layers(pool)`` (how many
    layers' decode attention runs a walk kernel on the pool: the
    engine's counters, ``ops.paged_attention.walk_plan``)."""

    name = "gpt"
    two_kind = False     # one block table a slot, one pool a tensor
    # What the family cannot serve, of ``prefix_cache``, ``spec_k``,
    # ``draft``, ``adapters``, ``prefill_chunk``, ``block_transfer``,
    # and why (the engine's refusals name both): nothing.
    refuses: Tuple[str, ...] = ()
    refuses_why = ""

    # What the programs of this file read through ``.astype(c)``: the
    # matmul weights that pass through ``resolve_weight`` with their
    # biases and, in an int8 tree, their scales (``*_q8`` stays int8),
    # and the two tables.  Everything else is read in its own dtype:
    # LayerNorm gains and biases in float32, and a routed GPT's
    # ``gate_w`` / ``moe_*`` leaves, whose compute dtype IS
    # ``gate_w.dtype`` (``models/gpt.py`` ``_moe_residual``).
    _CAST_TABLES = ("wte", "wpe", "wte_sc")
    _CAST_BLOCK_LEAVES = tuple(
        f"{w}{suffix}" for w in ("qkv", "proj", "mlp_in", "mlp_out")
        for suffix in ("_w", "_b", "_w_sc"))

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.prefill = functools.partial(paged_prefill, cfg)
        self.decode = functools.partial(paged_decode_step, cfg)

    def walk_layers(self, pool) -> int:
        """Layers whose decode attention runs ``rlt_paged_decode`` on
        ``pool`` (``attn_impl="auto"``): all or, off the kernel, none."""
        from ray_lightning_tpu.ops.paged_attention import (
            paged_decode_supported,
        )
        return self.cfg.n_layer if paged_decode_supported(pool["k"]) else 0

    def make_cache(self, num_blocks: int, block_size: int, num_slots: int,
                   dtype) -> "PagedKVCache":
        return PagedKVCache(self.cfg, num_blocks, block_size, dtype=dtype)

    def prepare_params(self, tree: Dict[str, Any],
                       compute_dtype) -> Dict[str, Any]:
        """The tree the programs are called with, made once at build:
        the leaves they would cast to ``compute_dtype`` on every call,
        held in it.  ``bf16(W)`` once is bit for bit ``bf16(W)`` per
        call, and the casts inside the programs become no-ops.  A leaf
        already in its dtype is handed on as it is, a tree with nothing
        to cast (float32 compute) is returned itself; the cast runs a
        leaf at a time, so the transient is one leaf."""
        c = jnp.dtype(compute_dtype)

        def stale(leaves, names):
            return [k for k in names if k in leaves and leaves[k].dtype != c]

        tables = stale(tree, self._CAST_TABLES)
        in_blocks = stale(tree["blocks"], self._CAST_BLOCK_LEAVES)
        if not tables and not in_blocks:
            return tree
        blocks = dict(tree["blocks"])
        for k in in_blocks:
            blocks[k] = blocks[k].astype(c)
        out = {**tree, "blocks": blocks}
        for k in tables:
            out[k] = out[k].astype(c)
        return out


def make_slot_keys(
    base_key: jax.Array,
    seeds: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Per-slot sampling keys ``fold_in(fold_in(base, seed), position)``.

    The serving sampler's whole RNG discipline: ``seed`` is stable per
    REQUEST (assigned at submit), ``position`` is the cache position of
    the logits being sampled — both deterministic functions of the
    request's own history, never of the batch around it.  So a request
    re-decoded after a recompute preemption (possibly in a different
    slot, among different neighbours) regenerates bitwise-identical
    tokens at any temperature, which is what makes the speculative
    rollback path (and index-based client dedup) safe beyond greedy.
    """
    def one(seed, p):
        return jax.random.fold_in(jax.random.fold_in(base_key, seed), p)

    return jax.vmap(one)(seeds, positions)


def sample_tokens(
    logits: jax.Array,
    keys: jax.Array,
    temperatures: jax.Array,
    top_ks: Optional[jax.Array] = None,
) -> jax.Array:
    """Per-slot sampling decision: greedy where ``temperature <= 0``,
    categorical at ``logits / temperature`` elsewhere, optionally
    truncated to the ``top_ks[w]`` highest-probability tokens.
    Shape-static ``(W, V)`` → ``(W,)`` int32 so it fuses into the
    decode/verify programs.

    Args:
        keys: ``(W,)`` per-slot PRNG keys (:func:`make_slot_keys`) —
            one independent stream per slot, so a slot's draw never
            depends on who else is in the batch.
        top_ks: optional ``(W,)`` int32 — ``k <= 0`` disables the
            truncation for that slot.  The filter is a full-vocab sort
            + threshold mask (k is an operand VALUE, never a shape), so
            any per-request k rides the same compiled program.

    Per-request top-p is intentionally not offered; greedy/temperature/
    top-k covers the serving SLO bench and the static path keeps the
    full sampler family.
    """
    greedy = jnp.argmax(logits, axis=-1)
    masked = logits
    if top_ks is not None:
        v = logits.shape[-1]
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(top_ks - 1, 0, v - 1)[:, None], axis=-1
        )
        masked = jnp.where(
            (top_ks > 0)[:, None] & (logits < kth), _NEG_INF, logits
        )
    temps = jnp.maximum(temperatures, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, masked / temps)
    return jnp.where(
        temperatures <= 0.0, greedy, sampled
    ).astype(jnp.int32)
