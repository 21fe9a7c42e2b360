"""Continuous batcher: admission queue, slot table, preemption policy.

jax-free host-side control plane for the serving engine.  The unit of
scheduling is the **slot** — one of ``num_slots`` rows of the compiled
decode program's fixed width.  Between decode steps the scheduler:

1. **expires** queued requests whose deadline passed (never admitted —
   cheaper to reject at the queue than to evict mid-decode);
2. **evicts** finished slots, freeing their blocks immediately;
3. **admits** queued requests while a free slot AND enough blocks for
   the request's prefill bucket exist (join-on-arrival: a request never
   waits for the running batch to drain);
4. **grows** active sequences one block at a time as they cross block
   boundaries.  When the pool is dry, the YOUNGEST active request is
   preempted (recompute-style: blocks freed, request requeued at the
   FRONT so it re-admits first) — latency already invested in old
   requests is never thrown away for a newcomer.

Everything here mutates small numpy arrays (block tables, seq lens,
temperatures) that the engine ships into the compiled step as operand
VALUES — admission and eviction never change a shape, so the scheduler
is recompile-free by construction.
"""

from __future__ import annotations

import enum
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_lightning_tpu.serve.kv_cache import (
    BlockAllocator, TRASH_BLOCK, extend_block_coverage, truncate_to,
)

__all__ = ["Request", "RequestState", "Scheduler", "default_buckets",
           "derive_geometry"]

# Deficit-round-robin "no grant yet" marker (None is a real key: the
# base model).
_RR_NEVER = object()


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    EXPIRED = "expired"     # deadline passed while queued
    REJECTED = "rejected"   # admission-queue backpressure


@dataclass
class Request:
    """One generation request and its runtime state."""

    rid: str
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    # Shape-static top-k truncation for temperature sampling (ridden as
    # an int32 operand value; None/0 = off).
    top_k: Optional[int] = None
    # Speculative-decoding draft count for this request: None = the
    # engine default, 0 = plain target decode, K > 0 = up to K drafted
    # tokens verified per tick (capped per tick by the tokens left).
    spec: Optional[int] = None
    # Multi-tenant LoRA: the adapter (tenant) this request decodes
    # through (None = the shared base model).  The engine resolves the
    # name to its pool slot at submit; the slot id rides the compiled
    # step as the per-slot ``adapter_ids`` operand.
    adapter: Optional[str] = None
    # Seconds from arrival the FIRST token must land by (TTFT SLO at
    # admission; None = no deadline).
    deadline_s: Optional[float] = None
    # Brownout shed class: 0 (default) sheds first when the router's
    # overload ladder reaches its shed rung; >= 1 keeps its seat.
    priority: int = 0
    # Called with (token_index, token_id) as tokens stream out; after a
    # preemption the engine re-emits from index 0 — consumers dedup on
    # the index (greedy regenerates identical tokens).
    on_token: Optional[Callable[[int, int], None]] = None

    # -- runtime (scheduler-owned) ------------------------------------------
    state: RequestState = RequestState.QUEUED
    arrival_t: float = field(default_factory=time.monotonic)
    # When the request's frame landed in the engine's inbox (queue
    # plane only; feeds the ``queue_wait_us`` counter, nothing else).
    recv_t: Optional[float] = None
    admitted_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finished_t: Optional[float] = None
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    preemptions: int = 0
    # Admission ordinal — the preemption victim ordering key.
    _seq_no: int = -1
    # The request's sampling-stream identity (kv_cache.make_slot_keys).
    # None = assigned from the submission ordinal ONCE at submit (never
    # re-assigned on preemption requeue), so a recompute re-decode
    # replays the exact same per-position key stream.  A PRESET value
    # survives submit untouched — the disaggregated router assigns
    # fleet-wide seeds so a failover re-submission to a DIFFERENT
    # replica regenerates the identical stream.
    sample_seed: Optional[int] = None
    # Distributed-tracing context (telemetry/propagate.TraceContext).
    # Set once at submit and NEVER cleared on preemption requeue, so a
    # recompute replay's spans land in the original trace.
    trace: Optional[object] = None
    # The adapter's resolved pool slot (engine-set at submit; 0 = the
    # NULL/base slot).  Stable across preemption requeues — the pool
    # refuses to remove an adapter any queued/active request holds.
    _adapter_slot: int = 0
    # Prompt tokens covered by a prefix-cache claim at the CURRENT
    # admission (0 = no shared prefix).  Re-derived on every admission:
    # a preempted request re-claims on requeue admission, and the cache
    # may have evicted (or grown) its chain in between.
    claimed_tokens: int = 0

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def done_reason(self) -> Optional[str]:
        if self.state is RequestState.FINISHED:
            return "eos" if (
                self.eos_token_id is not None
                and self.generated
                and self.generated[-1] == self.eos_token_id
            ) else "length"
        if self.state in (RequestState.EXPIRED, RequestState.REJECTED):
            return self.state.value
        return None


def default_buckets(block_size: int, max_prompt_len: int) -> List[int]:
    """Power-of-two block counts: ``block_size * (1, 2, 4, ...)`` up to
    the first bucket covering ``max_prompt_len``.  A handful of prefill
    programs covers every prompt length with <= 2x padding waste."""
    buckets = []
    b = block_size
    while True:
        buckets.append(b)
        if b >= max_prompt_len:
            return buckets
        b *= 2


def derive_geometry(serve_cfg, model_cfg) -> Tuple[int, List[int]]:
    """``(max_model_len, retained prefill buckets)`` for a serve config
    over a model config — THE one derivation rule, shared by
    :class:`~..engine.ServeEngine` and the disaggregated prefill
    workers (``serve/dist/prefill.py``), so a worker and its replicas
    can never disagree on bucket shapes (drift would fail every
    handoff at the replica's geometry check).

    A bucket longer than ``max_model_len`` cannot run (the prefill
    indexes the positional table at ``[0, T)``), so the longest
    RETAINED bucket bounds the admissible prompt length — the bound
    only bites when ``max_model_len`` is not bucket-aligned
    (docs/SERVING.md "Knobs")."""
    max_model_len = serve_cfg.max_model_len or model_cfg.seq_len
    buckets = list(serve_cfg.prefill_buckets or default_buckets(
        serve_cfg.block_size, max(1, max_model_len - 1)
    ))
    buckets = sorted(b for b in buckets if b <= max_model_len)
    if not buckets:
        raise ValueError(
            f"no prefill bucket fits max_model_len {max_model_len} "
            f"(block_size {serve_cfg.block_size} too large? smallest "
            f"bucket is one block)"
        )
    return max_model_len, buckets


class Scheduler:
    """Slot table + admission queue + block accounting.

    The engine drives it: ``poll()`` between decode steps returns what
    changed (admissions to prefill, expiries to report); ``append`` /
    ``finish`` / ``preempt_for_growth`` mutate per-slot state as tokens
    land.
    """

    def __init__(
        self,
        num_slots: int,
        allocator: BlockAllocator,
        block_size: int,
        max_blocks_per_seq: int,
        buckets: Sequence[int],
        max_queue: int = 64,
        max_queue_per_adapter: Optional[int] = None,
        window_allocator: Optional[BlockAllocator] = None,
        window_blocks: int = 0,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        for b in buckets:
            if b % block_size:
                raise ValueError(
                    f"prefill bucket {b} is not a multiple of the "
                    f"block size {block_size}"
                )
        self.num_slots = num_slots
        self.allocator = allocator
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.buckets = sorted(buckets)
        self.max_queue = max_queue
        # Per-tenant admission-queue bound: one tenant's burst must not
        # consume the whole shared queue (None = shared bound only).
        self.max_queue_per_adapter = max_queue_per_adapter
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * num_slots
        # Per-slot allocated physical blocks, in logical order.
        self._blocks: List[List[int]] = [[] for _ in range(num_slots)]
        # The compiled step's operands (value-only mutation).
        self.block_tables = np.full(
            (num_slots, max_blocks_per_seq), TRASH_BLOCK, np.int32
        )
        # A second kind of cache state (models with sliding-window
        # layers): every slot also holds a ring of ``window_blocks``
        # blocks of the window pool, claimed at admission from that
        # pool's own allocator and freed with the slot.  None = one
        # kind of state, the scheduler as it always was.
        self.window_allocator = window_allocator
        self.window_blocks = window_blocks if window_allocator else 0
        self._window: List[List[int]] = [[] for _ in range(num_slots)]
        self.window_tables = None if window_allocator is None else np.full(
            (num_slots, window_blocks), TRASH_BLOCK, np.int32
        )
        self.seq_lens = np.zeros((num_slots,), np.int32)
        self.temperatures = np.zeros((num_slots,), np.float32)
        self.top_ks = np.zeros((num_slots,), np.int32)
        self.sample_seeds = np.zeros((num_slots,), np.int32)
        # Draft-cache frontier per slot (speculative decoding): the
        # draft pool shares this table's block ids, valid through
        # position draft_lens[slot] - 1.  Trails seq_lens by at most 1
        # (the bonus-token tick), never leads it.
        self.draft_lens = np.zeros((num_slots,), np.int32)
        # Multi-tenant LoRA: each slot's adapter-pool slot id, ridden
        # into the compiled step as the ``adapter_ids`` operand (0 =
        # the NULL/base adapter — inactive slots gather a zero delta).
        self.adapter_slots = np.zeros((num_slots,), np.int32)
        self._admit_counter = 0
        self._submit_counter = 0
        # Prefix-cache / chunked-prefill hooks, engine-wired after
        # construction (all None/off = the pre-cache scheduler,
        # behaviour byte-identical).  ``claim_fn(req)`` returns
        # RETAINED shared-prefix block ids for a request at admission;
        # ``reclaim(n)`` asks the resident cache to evict up to ``n``
        # blocks when the pool runs dry (tried BEFORE preemption — a
        # resident chain is always cheaper to drop than a running
        # request); ``chunk_width`` admits prompts whose uncovered
        # suffix exceeds it with EXACT block coverage instead of a
        # prefill bucket (the suffix runs through the fixed-width chunk
        # program, which needs no bucket-shaped block set).
        self.claim_fn: Optional[Callable[[Request], List[int]]] = None
        self.reclaim: Optional[Callable[[int], int]] = None
        self.chunk_width: Optional[int] = None
        # Fairness state: the adapter key granted the LAST slot —
        # deficit-round-robin with a unit quantum (request costs are
        # uniform at admission: one slot, one bucket) cycles grants
        # across the tenants with queued work starting after this key.
        # The sentinel distinguishes "never granted" from "last grant
        # was the base (None) key".
        self._rr_last: object = _RR_NEVER

    # -- queue side ----------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def active_slots(self) -> int:
        return sum(r is not None for r in self.slots)

    def has_work(self) -> bool:
        return bool(self.queue) or self.active_slots > 0

    def queued_for(self, adapter: Optional[str]) -> int:
        """Queued requests for one adapter key (None = base model)."""
        return sum(1 for r in self.queue if r.adapter == adapter)

    def references_adapter(self, name: str) -> bool:
        """True while any queued or active request decodes through
        ``name`` — the engine's remove-adapter guard (freeing a slot a
        live request still gathers would serve it a neighbour's —
        or stale — delta)."""
        return any(r.adapter == name for r in self.queue) or any(
            r is not None and r.adapter == name for r in self.slots
        )

    def submit(self, req: Request) -> bool:
        """Enqueue, or reject (backpressure) when the shared queue — or
        the request's PER-ADAPTER bound — is full.  Rejection is
        synchronous and typed — the client decides whether to retry,
        never the server.  The per-adapter cap is the multi-tenant
        admission contract: one tenant's burst saturates its own bound
        and starts bouncing while every other tenant keeps its seats.
        """
        if len(self.queue) >= self.max_queue:
            req.state = RequestState.REJECTED
            return False
        if (self.max_queue_per_adapter is not None
                and self.queued_for(req.adapter)
                >= self.max_queue_per_adapter):
            req.state = RequestState.REJECTED
            return False
        req.state = RequestState.QUEUED
        if req.sample_seed is None:
            req.sample_seed = self._submit_counter
        self._submit_counter += 1
        self.queue.append(req)
        return True

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest prefill "
            f"bucket {self.buckets[-1]}"
        )

    # -- between-steps poll --------------------------------------------------
    def poll(
        self, now: Optional[float] = None
    ) -> Tuple[List[Tuple[int, Request, int]], List[Request]]:
        """Expire, then admit.  Returns ``(admissions, expired)`` where
        each admission is ``(slot, request, bucket_len)`` with blocks
        already allocated and the slot row populated — the engine only
        has to run the bucket's prefill program."""
        now = time.monotonic() if now is None else now
        expired: List[Request] = []
        fresh: Deque[Request] = deque()
        while self.queue:
            req = self.queue.popleft()
            # deadline_s is a TTFT-at-admission SLO: once a request has
            # been admitted and streamed (then got preempted back into
            # the queue), its deadline is already MET — expiring it on
            # requeue would throw away the invested latency the
            # front-requeue policy exists to protect.
            if (req.deadline_s is not None
                    and req.preemptions == 0
                    and now - req.arrival_t > req.deadline_s):
                req.state = RequestState.EXPIRED
                req.finished_t = now
                expired.append(req)
            else:
                fresh.append(req)
        self.queue = fresh

        admissions: List[Tuple[int, Request, int]] = []
        while self.queue:
            slot = next(
                (i for i, r in enumerate(self.slots) if r is None), None
            )
            if slot is None:
                break
            pick = self._next_grant_index()
            req = self.queue[pick]
            claimed: List[int] = []
            if self.claim_fn is not None:
                claimed = self.claim_fn(req)
            c_tokens = len(claimed) * self.block_size
            chunked = (self.chunk_width is not None
                       and getattr(req, "_handoff", None) is None
                       and (req.prompt_len - c_tokens > self.chunk_width
                            or req.prompt_len > self.buckets[-1]))
            if claimed or chunked:
                # Claimed and/or chunked admissions take exact coverage
                # (ceil(prompt/Bs) blocks, bucket sentinel 0): the
                # uncovered suffix runs through the engine's fixed-width
                # chunk program, so no bucket-shaped padding blocks are
                # needed — and prompts past the largest bucket admit.
                bucket = 0
                need = (-(-req.prompt_len // self.block_size)
                        - len(claimed))
            else:
                bucket = self.bucket_for(req.prompt_len)
                need = bucket // self.block_size
            ids = self._alloc(need)
            ring = None if ids is None else self._alloc_window()
            if ids is None or ring is None:
                if ids:
                    self.allocator.free(ids)
                if claimed:
                    self.allocator.free(claimed)  # drop the claim refs
                break  # pool dry: wait for evictions, keep grant order
            ids = claimed + ids
            req.claimed_tokens = c_tokens
            del self.queue[pick]
            if not req.preemptions:
                # Only ROTATION grants advance the fairness pointer: a
                # preempted request rides the priority lane, and letting
                # it move _rr_last would skip the tenants between the
                # last rotation grant and its key — one tenant's
                # repeated preemptions would systematically defer the
                # others a full cycle each time.
                self._rr_last = req.adapter
            req.state = RequestState.RUNNING
            req.slot = slot
            req.admitted_t = now
            req.generated = []
            req._seq_no = self._admit_counter
            self._admit_counter += 1
            self.slots[slot] = req
            self._blocks[slot] = ids
            row = self.block_tables[slot]
            row[:] = TRASH_BLOCK
            row[: len(ids)] = ids
            if ring:
                self._window[slot] = ring
                self.window_tables[slot, :] = ring
            self.seq_lens[slot] = req.prompt_len
            self.temperatures[slot] = req.temperature
            self.top_ks[slot] = req.top_k or 0
            self.sample_seeds[slot] = req.sample_seed
            self.draft_lens[slot] = req.prompt_len
            self.adapter_slots[slot] = req._adapter_slot
            admissions.append((slot, req, bucket))
        return admissions, expired

    def _next_grant_index(self) -> int:
        """Queue index of the next slot grant.

        Priority 1 — preempted requests, in queue order: the
        front-requeue contract (latency already invested is never
        thrown away) outranks fairness.  Priority 2 —
        deficit-round-robin over the adapter keys with queued work
        (unit quantum: every admission costs one slot and one bucket,
        so the deficit counter degenerates to strict rotation), FIFO
        within a key: the grant goes to the first key cyclically AFTER
        the last granted one, so one tenant's burst cannot monopolize
        slot turnover while others queue.  Single-key traffic (the
        whole pre-LoRA world: every request keys to the base model)
        reduces exactly to the old FIFO order.
        """
        for i, r in enumerate(self.queue):
            if r.preemptions:
                return i
        first_idx: Dict[Optional[str], int] = {}
        for i, r in enumerate(self.queue):
            if r.adapter not in first_idx:
                first_idx[r.adapter] = i
        if len(first_idx) == 1:
            return next(iter(first_idx.values()))

        def keypos(k: Optional[str]) -> Tuple[bool, str]:
            # Canonical cyclic order: base (None) first, then names.
            return (k is not None, k or "")

        order = sorted(first_idx, key=keypos)
        if self._rr_last is not _RR_NEVER:
            last = keypos(self._rr_last)
            for k in order:
                if keypos(k) > last:
                    return first_idx[k]
        return first_idx[order[0]]

    # -- per-step slot transitions ------------------------------------------
    def append_token(self, slot: int, token: int,
                     now: Optional[float] = None) -> bool:
        """Record one generated token for ``slot``; returns True when
        the request just finished (eos or length)."""
        now = time.monotonic() if now is None else now
        req = self.slots[slot]
        assert req is not None, f"append_token on empty slot {slot}"
        if req.first_token_t is None:
            req.first_token_t = now
        idx = len(req.generated)
        req.generated.append(token)
        if req.on_token is not None:
            try:
                req.on_token(idx, token)
            except Exception:  # noqa: BLE001 - a raising stream consumer
                # must never take the serve loop down with it
                import logging

                logging.getLogger(__name__).warning(
                    "serve: on_token callback raised for %s", req.rid,
                    exc_info=True,
                )
        done = (
            len(req.generated) >= req.max_new_tokens
            or (req.eos_token_id is not None and token == req.eos_token_id)
        )
        return done

    def needs_block(self, slot: int, upto_pos: Optional[int] = None) -> bool:
        """True when a write at ``upto_pos`` (default: the NEXT decode
        write, ``seq_lens[slot]``) crosses into an unallocated block.
        Speculative ticks pass ``seq_lens + width`` — the last position
        the verify window scatters."""
        pos = int(self.seq_lens[slot]) if upto_pos is None else int(upto_pos)
        return pos // self.block_size >= len(self._blocks[slot])

    def _alloc(self, n: int) -> Optional[List[int]]:
        """:meth:`BlockAllocator.alloc` with one reclaim retry: when the
        pool is dry and a prefix cache is wired, ask it to evict enough
        resident (idle) blocks first — dropping a cached chain is
        always cheaper than preempting a running request."""
        ids = self.allocator.alloc(n)
        if ids is None and self.reclaim is not None:
            self.reclaim(n - self.allocator.free_blocks)
            ids = self.allocator.alloc(n)
        return ids

    def _alloc_window(self) -> Optional[List[int]]:
        """The slot's ring of window blocks ([] when the model has one
        kind of state), or None when the window pool is dry."""
        if self.window_allocator is None:
            return []
        return self.window_allocator.alloc(self.window_blocks)

    def grow(self, slot: int) -> bool:
        """Allocate the next block for ``slot``.  False = pool dry."""
        if len(self._blocks[slot]) >= self.max_blocks_per_seq:
            raise RuntimeError(
                f"slot {slot} exceeded max_blocks_per_seq "
                f"{self.max_blocks_per_seq} — engine admission bound bug"
            )
        ids = self._alloc(1)
        if ids is None:
            return False
        self._blocks[slot].extend(ids)
        self.block_tables[slot, len(self._blocks[slot]) - 1] = ids[0]
        return True

    def append_tokens(self, slot: int, tokens: Sequence[int],
                      now: Optional[float] = None) -> Tuple[int, bool]:
        """Record a TICK's worth of generated tokens for ``slot`` —
        the variable-width emission of a speculative verify (accepted
        prefix + corrected/bonus token).  Stops early at eos or the
        request's ``max_new_tokens``; returns ``(n_emitted, done)``.
        ``on_token`` fires per token with its stream index, exactly as
        the one-token path does, so client-side index dedup is
        width-agnostic."""
        req = self.slots[slot]
        assert req is not None, f"append_tokens on empty slot {slot}"
        emitted = 0
        for tok in tokens:
            if len(req.generated) >= req.max_new_tokens:
                return emitted, True
            done = self.append_token(slot, int(tok), now=now)
            emitted += 1
            if done:
                return emitted, True
        return emitted, len(req.generated) >= req.max_new_tokens

    def truncate_slot_to(self, slot: int, n_tokens: int) -> int:
        """Roll the slot's cache coverage back to ``n_tokens`` positions
        (the post-accept frontier of a speculative tick): ``seq_lens``
        shrinks to the value, blocks past the covering prefix return to
        the pool, their table entries go back to trash.  Returns blocks
        freed."""
        freed = truncate_to(
            self.allocator, self._blocks[slot], self.block_tables[slot],
            n_tokens, self.block_size,
        )
        self.seq_lens[slot] = n_tokens
        self.draft_lens[slot] = min(int(self.draft_lens[slot]), n_tokens)
        return freed

    def cover(self, slot: int, upto_pos: int) -> bool:
        """Multi-block :meth:`grow`: allocate until position
        ``upto_pos`` is writable (all-or-nothing).  False = pool dry."""
        if upto_pos // self.block_size >= self.max_blocks_per_seq:
            raise RuntimeError(
                f"slot {slot} coverage request past max_blocks_per_seq "
                f"{self.max_blocks_per_seq} — engine width-cap bug"
            )
        ok = extend_block_coverage(
            self.allocator, self._blocks[slot], self.block_tables[slot],
            upto_pos, self.block_size,
        )
        if not ok and self.reclaim is not None:
            need = (upto_pos // self.block_size) + 1 \
                - len(self._blocks[slot])
            self.reclaim(need - self.allocator.free_blocks)
            ok = extend_block_coverage(
                self.allocator, self._blocks[slot],
                self.block_tables[slot], upto_pos, self.block_size,
            )
        return ok

    def cow_slot(self, slot: int, upto_block: int
                 ) -> Optional[Tuple[List[int], List[int]]]:
        """Copy-on-write bookkeeping for ``slot``: every SHARED block
        (refcount > 1) among its first ``upto_block`` blocks is swapped
        for a freshly allocated private one — table entries and the
        slot's block list point at the copies, references on the
        originals are dropped.  Returns ``(src_ids, dst_ids)`` for the
        engine's ``copy_blocks`` program (empty lists = nothing
        shared), or ``None`` when the pool cannot cover the copies
        (nothing mutated: all-or-nothing, like every alloc here).

        The admission claim cap keeps nominal serving from ever needing
        this (writes land strictly past the shared frontier) — it is
        the escape hatch for any path that must WRITE below it.
        """
        blocks = self._blocks[slot]
        shared = [i for i in range(min(upto_block, len(blocks)))
                  if self.allocator.is_shared(blocks[i])]
        if not shared:
            return [], []
        fresh = self._alloc(len(shared))
        if fresh is None:
            return None
        src = [blocks[i] for i in shared]
        for i, dst in zip(shared, fresh):
            blocks[i] = dst
            self.block_tables[slot, i] = dst
        self.allocator.free(src)
        return src, fresh

    def preempt_youngest(self, protect: Optional[int] = None
                         ) -> Optional[Request]:
        """Evict the most recently admitted active request (recompute
        preemption): free its blocks, requeue it at the FRONT.  Returns
        the victim, or None when no slot (other than ``protect``) is
        evictable."""
        victims = [
            (req._seq_no, slot)
            for slot, req in enumerate(self.slots)
            if req is not None and slot != protect
        ]
        if not victims:
            return None
        _, slot = max(victims)
        req = self.slots[slot]
        self._release(slot)
        req.state = RequestState.QUEUED
        req.slot = None
        req.preemptions += 1
        req.generated = []
        req.first_token_t = None
        self.queue.appendleft(req)
        return req

    def adopt(self, req: Request, ids: List[int], seq_len: int,
              now: Optional[float] = None) -> Optional[int]:
        """Place an ALREADY-RUNNING request (a live-KV migration
        import) directly into a free slot: ``ids`` are blocks the
        caller allocated from THIS scheduler's pool and scattered the
        imported KV into; ``seq_len`` is the KV frontier those blocks
        cover.  Mirrors :meth:`poll`'s slot population exactly — minus
        the queue/claim bookkeeping the request already paid on its
        draining home replica.  Returns the slot, or None when no slot
        is free (the caller falls back to recompute resubmission)."""
        now = time.monotonic() if now is None else now
        slot = next(
            (i for i, r in enumerate(self.slots) if r is None), None
        )
        if slot is None:
            return None
        req.state = RequestState.RUNNING
        req.slot = slot
        if req.admitted_t is None:
            req.admitted_t = now
        if req.first_token_t is None and req.generated:
            req.first_token_t = now
        req._seq_no = self._admit_counter
        self._admit_counter += 1
        self.slots[slot] = req
        self._blocks[slot] = list(ids)
        row = self.block_tables[slot]
        row[:] = TRASH_BLOCK
        row[: len(ids)] = ids
        self.seq_lens[slot] = seq_len
        self.temperatures[slot] = req.temperature
        self.top_ks[slot] = req.top_k or 0
        self.sample_seeds[slot] = req.sample_seed
        self.draft_lens[slot] = seq_len
        self.adapter_slots[slot] = req._adapter_slot
        return slot

    def cancel(self, rid: str) -> Optional[Request]:
        """Drop ``rid`` wherever it is — queued (removed) or active
        (slot released, blocks freed).  Returns the request (terminal
        status is the CALLER's call — the hedge cancel path reports
        ``cancelled``, never a client-visible state), or None when the
        rid is unknown here."""
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                del self.queue[i]
                r.slot = None
                return r
        for slot, r in enumerate(self.slots):
            if r is not None and r.rid == rid:
                self._release(slot)
                r.slot = None
                return r
        return None

    def finish(self, slot: int, now: Optional[float] = None) -> Request:
        now = time.monotonic() if now is None else now
        req = self.slots[slot]
        assert req is not None, f"finish on empty slot {slot}"
        req.state = RequestState.FINISHED
        req.finished_t = now
        req.slot = None
        self._release(slot)
        return req

    def _release(self, slot: int) -> None:
        self.allocator.free(self._blocks[slot])
        self._blocks[slot] = []
        if self._window[slot]:
            self.window_allocator.free(self._window[slot])
            self._window[slot] = []
            self.window_tables[slot, :] = TRASH_BLOCK
        self.slots[slot] = None
        self.block_tables[slot, :] = TRASH_BLOCK
        self.seq_lens[slot] = 0
        self.temperatures[slot] = 0.0
        self.top_ks[slot] = 0
        self.sample_seeds[slot] = 0
        self.draft_lens[slot] = 0
        self.adapter_slots[slot] = 0

    # -- introspection -------------------------------------------------------
    def snapshot(self) -> dict:
        return {
            "queue_depth": self.queue_depth,
            "slots_active": self.active_slots,
            "num_slots": self.num_slots,
            "blocks_free": self.allocator.free_blocks,
            "blocks_live": self.allocator.live_blocks,
            "num_blocks": self.allocator.num_blocks,
            **({} if self.window_allocator is None else {
                "window_blocks_free": self.window_allocator.free_blocks,
                "window_blocks_live": self.window_allocator.live_blocks,
            }),
        }
