"""Prefix-aware KV reuse + chunked prefill.

The correctness bar mirrors the serving plane's: a request whose
prompt shares a resident prefix chain must produce EXACTLY the tokens
the static ``generate()`` reference produces — greedy AND sampled —
because claiming is refcount bookkeeping, never recompute.  On top:
the allocator's refcount/COW discipline (sharing never enables a
double-free; eviction never takes a block a live chain holds), the
radix index units (match / insert / mid-edge split / LRU eviction),
the scheduler's claim + reclaim hooks (evict-before-preempt), COW
bookkeeping, chunked prefill's no-stall bound (a long admission never
starves resident decode slots for more than one chunk tick — pinned
via per-tick token emission), and adapter-drop invalidation.
"""

import pytest

import jax

from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.kv_cache import (
    TRASH_BLOCK, BlockAllocator, PrefixIndex,
)
from ray_lightning_tpu.serve.scheduler import Request, Scheduler
from ray_lightning_tpu.telemetry import compile_event_count

from utils import rand_prompt as _rand_prompt
from utils import reference_tokens as _ref_tokens
from utils import tiny_gpt

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    return tiny_gpt()


# ---------------------------------------------------------------------------
# BlockAllocator: refcount discipline (jax-free)
# ---------------------------------------------------------------------------

class TestAllocatorRefcounts:
    def test_retain_free_lifecycle(self):
        a = BlockAllocator(6)
        ids = a.alloc(2)
        b = ids[0]
        assert a.refcount(b) == 1 and not a.is_shared(b)
        a.retain([b])
        assert a.refcount(b) == 2 and a.is_shared(b)
        free_before = a.free_blocks
        a.free([b])                        # drops to 1: still live
        assert a.refcount(b) == 1
        assert a.free_blocks == free_before
        a.free([b])                        # drops to 0: returns to pool
        assert a.refcount(b) == 0
        assert a.free_blocks == free_before + 1
        a.free([ids[1]])

    def test_shared_block_double_free_still_raises(self):
        """Sharing widens the legal free count to the refcount — one
        PAST it is still the hard error."""
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.retain([b])
        a.free([b])
        a.free([b])
        with pytest.raises(RuntimeError, match="double-free"):
            a.free([b])

    def test_retain_dead_block_raises(self):
        a = BlockAllocator(4)
        (b,) = a.alloc(1)
        a.free([b])
        with pytest.raises(RuntimeError, match="not live"):
            a.retain([b])

    def test_shared_block_survives_one_owner(self):
        """The chain-resident case: request frees its blocks, the
        index's reference keeps them out of the free list — a fresh
        alloc never hands out a block a chain still holds."""
        a = BlockAllocator(4)                 # 3 usable
        ids = a.alloc(3)
        a.retain(ids)                         # the "index" reference
        a.free(ids)                           # the "request" reference
        assert all(a.refcount(b) == 1 for b in ids)
        assert a.alloc(1) is None             # nothing actually freed
        a.free(ids)
        assert a.free_blocks == 3


# ---------------------------------------------------------------------------
# PrefixIndex: radix units (jax-free)
# ---------------------------------------------------------------------------

def _tok(n, base=0):
    return [base + i + 1 for i in range(n)]


class TestPrefixIndex:
    def _index(self, num_blocks=16, block_size=4):
        a = BlockAllocator(num_blocks)
        return a, PrefixIndex(a, block_size)

    def _insert(self, a, idx, key, tokens):
        """Prefill-sim: alloc the full blocks, insert, free the
        request's own references (the index keeps its retains)."""
        ids = a.alloc(len(tokens) // idx.block_size)
        idx.insert(key, tokens, ids)
        a.free(ids)
        return ids

    def test_insert_claim_roundtrip(self):
        a, idx = self._index()
        toks = _tok(12)                       # 3 full blocks
        ids = self._insert(a, idx, None, toks)
        got = idx.claim(None, toks, max_blocks=3)
        assert got == ids
        assert all(a.refcount(b) == 2 for b in got)  # claim retained
        a.free(got)
        assert idx.stats()["hits"] == 1
        assert idx.stats()["blocks_claimed"] == 3

    def test_claim_cap_and_partial_edge(self):
        a, idx = self._index()
        toks = _tok(16)                       # one 4-block edge
        ids = self._insert(a, idx, None, toks)
        # Cap below the edge length: partial-edge match, 2 blocks.
        got = idx.claim(None, toks, max_blocks=2)
        assert got == ids[:2]
        a.free(got)
        # Diverging tokens mid-edge: only the shared blocks match.
        fork = toks[:8] + _tok(8, base=100)
        got = idx.claim(None, fork, max_blocks=4)
        assert got == ids[:2]
        a.free(got)

    def test_claim_miss_and_zero_cap(self):
        a, idx = self._index()
        assert idx.claim(None, _tok(8), max_blocks=2) == []
        self._insert(a, idx, None, _tok(8))
        assert idx.claim(None, _tok(8), max_blocks=0) == []
        st = idx.stats()
        assert st["lookups"] == 2 and st["hits"] == 0

    def test_mid_edge_split(self):
        a, idx = self._index()
        long = _tok(16)
        ids = self._insert(a, idx, None, long)
        # Shares 2 of the 4 blocks, then diverges: splits the edge.
        fork = long[:8] + _tok(8, base=50)
        fork_ids = a.alloc(4)
        added = idx.insert(None, fork, fork_ids)
        assert added == 2                     # only the new suffix
        a.free(fork_ids)
        # Both chains stay fully claimable after the split.
        got = idx.claim(None, long, max_blocks=4)
        assert got == ids
        a.free(got)
        got = idx.claim(None, fork, max_blocks=4)
        assert got == ids[:2] + fork_ids[2:]
        a.free(got)

    def test_insert_covered_is_free(self):
        a, idx = self._index()
        toks = _tok(12)
        self._insert(a, idx, None, toks)
        cached = idx.stats()["cached_blocks"]
        ids = a.alloc(3)
        assert idx.insert(None, toks, ids) == 0   # walk matches, no-op
        a.free(ids)
        assert idx.stats()["cached_blocks"] == cached

    def test_insert_short_ids_raises(self):
        a, idx = self._index()
        with pytest.raises(ValueError, match="full blocks"):
            idx.insert(None, _tok(12), a.alloc(2))

    def test_keys_are_isolated(self):
        """One tenant's chain never satisfies another's lookup."""
        a, idx = self._index()
        toks = _tok(8)
        self._insert(a, idx, "tenant-a", toks)
        assert idx.claim("tenant-b", toks, max_blocks=2) == []
        assert idx.claim(None, toks, max_blocks=2) == []

    def test_evict_lru_and_refcount_pin(self):
        a, idx = self._index(num_blocks=16)
        cold = self._insert(a, idx, None, _tok(8))           # older
        hot = self._insert(a, idx, None, _tok(8, base=40))   # newer
        held = idx.claim(None, _tok(8, base=40), max_blocks=2)
        assert held == hot
        # Ask for everything: the LRU chain goes, the claimed (shared,
        # refcount 2) chain is pinned — NEVER evicted under a live
        # claim.
        freed = idx.evict(4)
        assert freed == 2
        assert idx.stats()["blocks_evicted"] == 2
        assert all(a.refcount(b) == 0 for b in cold)
        assert all(a.refcount(b) == 2 for b in hot)
        a.free(held)
        assert idx.evict(4) == 2              # now droppable
        assert idx.stats()["cached_blocks"] == 0

    def test_evict_tail_first_preserves_prefix(self):
        """Partial eviction trims chains from the tail: the surviving
        prefix must still match (chain integrity)."""
        a, idx = self._index()
        toks = _tok(16)
        ids = self._insert(a, idx, None, toks)
        assert idx.evict(1) == 1              # drops ids[-1] only
        got = idx.claim(None, toks, max_blocks=4)
        assert got == ids[:3]
        a.free(got)

    def test_drop_key_and_drop_all(self):
        a, idx = self._index()
        self._insert(a, idx, "t0", _tok(8))
        self._insert(a, idx, None, _tok(8, base=30))
        assert idx.drop("t0") == 2
        assert idx.claim("t0", _tok(8), max_blocks=2) == []
        assert idx.drop_all() == 2
        assert a.free_blocks == a.num_blocks - 1
        assert idx.drop("t0") == 0            # idempotent


# ---------------------------------------------------------------------------
# Scheduler: claim admission, evict-before-preempt, COW (jax-free)
# ---------------------------------------------------------------------------

def _sched(num_blocks=16, **kw):
    alloc = BlockAllocator(num_blocks)
    args = dict(num_slots=2, block_size=4, max_blocks_per_seq=4,
                buckets=[4, 8, 16], max_queue=4)
    args.update(kw)
    return Scheduler(args.pop("num_slots"), alloc, **args)


def _req(rid, prompt_len, **kw):
    return Request(rid=rid, prompt=_tok(prompt_len),
                   max_new_tokens=kw.pop("max_new_tokens", 4), **kw)


class TestSchedulerClaim:
    def test_claimed_admission_exact_coverage(self):
        s = _sched()
        claimed = s.allocator.alloc(2)        # pretend-resident chain
        s.allocator.retain(claimed)           # the claim's reference
        s.claim_fn = lambda req: list(claimed)
        s.submit(_req("r1", 11))              # ceil(11/4) = 3 blocks
        (adm,), _ = s.poll()
        slot, req, bucket = adm
        assert bucket == 0                    # exact-coverage sentinel
        assert req.claimed_tokens == 8
        assert s._blocks[slot][:2] == claimed
        assert len(s._blocks[slot]) == 3      # claimed + 1 fresh
        row = s.block_tables[slot]
        assert row[3] == TRASH_BLOCK
        s.finish(slot)                        # frees the claim refs too
        assert [s.allocator.refcount(b) for b in claimed] == [1, 1]

    def test_reclaim_runs_before_admission_fails(self):
        """Pool dry at admission: the reclaim hook (cache eviction) is
        consulted before the grant stalls — a resident chain is always
        cheaper than a waiting request."""
        s = _sched(num_blocks=5)              # 4 usable
        resident = s.allocator.alloc(3)       # cache-held blocks
        calls = []

        def reclaim(n):
            calls.append(n)
            s.allocator.free(resident[:n])
            return n

        s.reclaim = reclaim
        s.submit(_req("r1", 16))              # needs all 4 blocks
        (adm,), _ = s.poll()
        assert adm[2] == 16
        assert calls == [3]

    def test_claim_refs_dropped_when_pool_dry(self):
        """An admission that claims but cannot cover its suffix must
        drop the claim references (no leak, no double-retain when the
        request is re-granted later)."""
        s = _sched(num_blocks=4)              # 3 usable
        chain = s.allocator.alloc(2)
        s.allocator.retain(chain)
        s.claim_fn = lambda req: (s.allocator.retain(chain),
                                  list(chain))[1]
        s.allocator.alloc(1)                  # drain the pool
        s.submit(_req("r1", 16))              # needs 2 fresh: dry
        adms, _ = s.poll()
        assert adms == []
        assert [s.allocator.refcount(b) for b in chain] == [2, 2]

    def test_cow_slot(self):
        s = _sched()
        s.submit(_req("r1", 16))
        ((slot, _, _),), _ = s.poll()
        assert s.cow_slot(slot, 4) == ([], [])      # nothing shared
        shared = s._blocks[slot][:2]
        s.allocator.retain(shared)                  # now refcount 2
        src, dst = s.cow_slot(slot, 2)
        assert src == shared and len(dst) == 2
        assert s._blocks[slot][:2] == dst
        assert list(s.block_tables[slot][:2]) == dst
        assert [s.allocator.refcount(b) for b in shared] == [1, 1]
        s.allocator.free(shared)

    def test_cow_slot_pool_dry_mutates_nothing(self):
        s = _sched(num_blocks=5)              # 4 usable
        s.submit(_req("r1", 16))              # takes all 4
        ((slot, _, _),), _ = s.poll()
        shared = s._blocks[slot][:1]
        s.allocator.retain(shared)
        before = list(s._blocks[slot])
        assert s.cow_slot(slot, 4) is None
        assert s._blocks[slot] == before
        assert s.allocator.refcount(shared[0]) == 2
        s.allocator.free(shared)


# ---------------------------------------------------------------------------
# Engine: shared-prefix parity, chunked no-stall, invalidation
# ---------------------------------------------------------------------------

class TestPrefixEngine:
    def test_shared_prefix_parity_greedy_and_sampled(self, model):
        """The tentpole contract: a claim-served request is bitwise the
        static reference, greedy and at temperature>0 — and the second
        request actually HITS the cache."""
        m, params = model
        shared = _rand_prompt(5, 18)          # 2 full blocks @ Bs=8
        p1 = shared + _rand_prompt(6, 4)
        p2 = shared + _rand_prompt(7, 6)
        eng = ServeEngine(m, params,
                          ServeConfig(num_slots=2, block_size=8,
                                      prefix_cache=True))
        try:
            t1 = eng.generate(p1, 8)
            assert eng.prefix_cache.stats()["cached_blocks"] >= 2
            t2 = eng.generate(p2, 8)
            t2s = eng.generate(p2, 8, temperature=0.8, sample_seed=11)
            st = eng.prefix_cache.stats()
            assert st["hits"] >= 2 and st["blocks_claimed"] >= 4
            assert t1 == _ref_tokens(m, params, p1, 8)
            assert t2 == _ref_tokens(m, params, p2, 8)
            # Sampled arm: reference is the SAME seed served by a
            # cache-less engine (the static path doesn't sample).
            ref = ServeEngine(m, params,
                              ServeConfig(num_slots=2, block_size=8))
            try:
                t2s_ref = ref.generate(p2, 8, temperature=0.8,
                                       sample_seed=11)
            finally:
                ref.stop()
            assert t2s == t2s_ref
        finally:
            eng.stop()

    def test_steady_state_hit_zero_recompiles(self, model):
        m, params = model
        prompt = _rand_prompt(8, 24)
        eng = ServeEngine(m, params,
                          ServeConfig(num_slots=2, block_size=8,
                                      prefix_cache=True))
        try:
            ref = eng.generate(prompt, 6)
            # First claimed replay warms the suffix program (the
            # chunk executable at the smallest bucket covering the
            # uncovered tail — compiled once, like any bucket).
            warm = eng.generate(prompt, 6)
            assert warm == ref
            before = compile_event_count()
            again = eng.generate(prompt, 6)
            assert again == ref
            assert compile_event_count() - before == 0
            assert eng.prefix_cache.stats()["hits"] >= 2
        finally:
            eng.stop()

    def test_chunked_prefill_never_stalls_residents(self, model):
        """The no-stall pin, per-tick token emission: while a long
        prompt chunks in, every resident decode slot emits on every
        step except at most ONE chunk tick in a row."""
        m, params = model
        eng = ServeEngine(m, params,
                          ServeConfig(num_slots=3, block_size=8,
                                      prefill_chunk=16))
        long_prompt = _rand_prompt(9, 48)
        try:
            eng.generate(_rand_prompt(10, 12), 2)     # warm short path
            eng.generate(_rand_prompt(11, 48), 2)     # warm chunk path
            emitted = {0: 0, 1: 0}
            residents = [
                eng.submit(_rand_prompt(12 + i, 12), 48,
                           on_token=lambda idx, tok, i=i:
                           emitted.__setitem__(i, emitted[i] + 1))
                for i in (0, 1)
            ]
            while not all(emitted.values()):
                eng.step()
            first_long = []
            h = eng.submit(long_prompt, 4,
                           on_token=lambda idx, tok:
                           first_long.append(tok))
            stall, max_stall = {0: 0, 1: 0}, 0
            while not first_long:
                seen = dict(emitted)
                assert eng.step()
                for i in (0, 1):
                    stall[i] = 0 if emitted[i] > seen[i] else stall[i] + 1
                    max_stall = max(max_stall, stall[i])
            assert max_stall <= 1, f"resident stalled {max_stall} ticks"
            eng.run_until_idle()
            assert h.result(0) == _ref_tokens(m, params, long_prompt, 4)
            assert all(r.done() for r in residents)
            assert eng.stats.counters.get("prefill_chunks", 0) >= 2
        finally:
            eng.stop()

    def test_cache_pressure_evicts_not_preempts(self, model):
        """A full pool of resident chains yields to admissions via the
        reclaim hook — running requests are never preempted to make
        room while evictable cache blocks exist."""
        m, params = model
        eng = ServeEngine(m, params,
                          ServeConfig(num_slots=2, block_size=8,
                                      # 10 usable: 4 chains (8 resident
                                      # blocks) leave 2 free, the next
                                      # bucket-32 admission needs 4 —
                                      # MUST reclaim, never preempt.
                                      num_blocks=11,
                                      prefix_cache=True))
        try:
            for s in range(4):                # fill the pool with chains
                eng.generate(_rand_prompt(20 + s, 17), 2)
            assert eng.prefix_cache.stats()["cached_blocks"] >= 4
            prompt = _rand_prompt(30, 17)
            toks = eng.generate(prompt, 4)
            assert toks == _ref_tokens(m, params, prompt, 4)
            assert eng.prefix_cache.stats()["blocks_evicted"] > 0
            assert eng.stats.counters.get("preemptions", 0) == 0
        finally:
            eng.stop()

    def test_adapter_drop_invalidates_chains(self, model):
        """Replacing an adapter drops its chains (stale KV) without
        touching the base key's."""
        import dataclasses

        from ray_lightning_tpu.models.gpt import synthetic_lora_adapter

        m, params = model
        lora_cfg = dataclasses.replace(m.config, lora_rank=4)
        ad_a, merged_a = synthetic_lora_adapter(
            params, lora_cfg, jax.random.PRNGKey(31))
        ad_b, _ = synthetic_lora_adapter(
            params, lora_cfg, jax.random.PRNGKey(32))
        eng = ServeEngine(m, params,
                          ServeConfig(num_slots=2, block_size=8,
                                      max_adapters=2, adapter_rank=4,
                                      prefix_cache=True),
                          adapters={"t": ad_a})
        prompt = _rand_prompt(40, 18)
        try:
            ref = eng.generate(prompt, 6, adapter="t")
            assert ref == _ref_tokens(m, merged_a, prompt, 6)
            eng.generate(prompt, 6)           # base chain, same tokens
            assert "t" in eng.prefix_cache._roots
            eng.add_adapter("t", ad_b)        # hot-replace: stale KV
            eng.generate(prompt, 2)           # a step processes drops
            assert "t" not in eng.prefix_cache._roots
            assert None in eng.prefix_cache._roots  # base chain kept
            hits_before = eng.prefix_cache.stats()["hits"]
            # The t-keyed lookup after the drop must MISS (the stale
            # chain is gone) and the fresh chain re-registers.
            eng.generate(prompt, 6, adapter="t")
            assert eng.prefix_cache.stats()["hits"] == hits_before
            assert "t" in eng.prefix_cache._roots
        finally:
            eng.stop()
