"""Speculative decoding on the serving plane: draft-propose /
target-verify over the paged KV cache.

The correctness bar extends round 11's contract: a request served
SPECULATIVELY must produce exactly the tokens the non-speculative
engine (and the static ``generate()`` path) would — the lossless-
speculation guarantee, pinned bitwise for greedy.  On top: the verify
program's logits parity against the full forward, shape-static top-k
sampling vs a host reference, multi-token append / rollback block
arithmetic, the zero-recompile steady state with the draft+verify
program set, temperature>0 reproducibility across recompute preemption
(the rollback path's load-bearing contract), and client-side index
dedup under variable-width emission.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.gpt import GPT, GPTConfig
from ray_lightning_tpu.serve.client import ServeClient
from ray_lightning_tpu.serve.draft import (
    early_exit_draft, pad_identity_layers,
)
from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.kv_cache import (
    TRASH_BLOCK, BlockAllocator, PagedKVCache, extend_block_coverage,
    make_slot_keys, paged_verify_step, sample_tokens, truncate_to,
)
from ray_lightning_tpu.telemetry import compile_event_count

from utils import rand_prompt as _rand_prompt
from utils import reference_tokens as _ref_tokens
from utils import rlt_top_once, tiny_gpt

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    """4-layer target whose 2-layer early-exit is the draft."""
    m, params = tiny_gpt(n_layer=4)
    draft, draft_params = early_exit_draft(m, params, 2)
    return m, params, draft, draft_params


def _spec_engine(m, params, draft, draft_params, spec_k=3, **cfg_kw):
    kw = dict(num_slots=3, block_size=8)
    kw.update(cfg_kw)
    return ServeEngine(
        m, params, ServeConfig(spec_k=spec_k, **kw),
        draft_module=draft, draft_params=draft_params,
    )


# ---------------------------------------------------------------------------
# Block arithmetic: multi-token coverage + rollback (jax-free units)
# ---------------------------------------------------------------------------

class TestBlockArithmetic:
    def test_extend_coverage_all_or_nothing(self):
        alloc = BlockAllocator(6)  # 5 usable
        blocks, row = [], np.full((8,), TRASH_BLOCK, np.int32)
        assert extend_block_coverage(alloc, blocks, row, 7, 4)  # 2 blocks
        assert len(blocks) == 2 and alloc.free_blocks == 3
        assert list(row[:2]) == blocks
        # Already covered: no-op.
        assert extend_block_coverage(alloc, blocks, row, 5, 4)
        assert len(blocks) == 2
        # 4 more blocks needed, only 3 free: nothing is taken.
        assert not extend_block_coverage(alloc, blocks, row, 23, 4)
        assert len(blocks) == 2 and alloc.free_blocks == 3

    def test_truncate_frees_tail_and_restores_trash(self):
        alloc = BlockAllocator(8)
        blocks, row = [], np.full((8,), TRASH_BLOCK, np.int32)
        assert extend_block_coverage(alloc, blocks, row, 15, 4)  # 4 blocks
        kept = list(blocks)
        freed = truncate_to(alloc, blocks, row, 6, 4)  # covers 2 blocks
        assert freed == 2 and blocks == kept[:2]
        assert (row[2:] == TRASH_BLOCK).all()
        assert alloc.free_blocks == 7 - 2
        # Freed blocks are immediately reusable.
        assert alloc.alloc(5) is not None

    def test_truncate_to_zero(self):
        alloc = BlockAllocator(4)
        blocks, row = [], np.full((4,), TRASH_BLOCK, np.int32)
        extend_block_coverage(alloc, blocks, row, 3, 4)
        assert truncate_to(alloc, blocks, row, 0, 4) == 1
        assert blocks == [] and alloc.free_blocks == 3

    def test_scheduler_truncate_slot(self):
        from ray_lightning_tpu.serve.scheduler import Request, Scheduler

        alloc = BlockAllocator(10)
        s = Scheduler(1, alloc, block_size=4, max_blocks_per_seq=6,
                      buckets=[4, 8])
        s.submit(Request(rid="a", prompt=[1, 2, 3], max_new_tokens=8))
        (slot, req, _), = s.poll(now=0.0)[0]
        assert s.cover(slot, 14)  # 4 blocks total
        assert len(s._blocks[slot]) == 4
        s.seq_lens[slot] = 15
        s.truncate_slot_to(slot, 5)
        assert int(s.seq_lens[slot]) == 5
        assert len(s._blocks[slot]) == 2
        assert (s.block_tables[slot, 2:] == TRASH_BLOCK).all()


# ---------------------------------------------------------------------------
# Verify program vs the full forward (device parity)
# ---------------------------------------------------------------------------

class TestVerifyParity:
    def test_verify_window_logits_match_full_forward(self, model):
        """Teacher-forcing a (K+1)-token window through
        paged_verify_step reproduces the full forward's logits at every
        window position — across block boundaries, on scattered
        physical blocks, mid-sequence."""
        m, params, _, _ = model
        cfg = m.config
        toks = np.asarray(_rand_prompt(2, 15, cfg.vocab_size))
        full = np.asarray(m.forward(params, jnp.asarray([toks])))
        cache = PagedKVCache(cfg, num_blocks=16, block_size=4)
        pool = cache.init_pool()
        phys = [5, 1, 7, 3]
        bt = np.full((2, 4), TRASH_BLOCK, np.int32)
        bt[0, :4] = phys
        seq_lens = np.zeros((2,), np.int32)
        T = 5  # window width: tokens [0, 5), then [5, 10), then [10, 15)
        for start in range(0, 15, T):
            window = np.zeros((2, T), np.int32)
            window[0] = toks[start: start + T]
            limits = np.asarray([start + T, 0], np.int32)
            logits, pool = paged_verify_step(
                cfg, params, pool, jnp.asarray(bt),
                jnp.asarray(seq_lens), jnp.asarray(window),
                jnp.asarray(limits),
            )
            np.testing.assert_allclose(
                np.asarray(logits)[0], full[0, start: start + T],
                rtol=1e-4, atol=1e-4,
            )
            seq_lens[0] += T

    def test_write_limit_trashes_pad_positions(self, model):
        """Window positions at/past the limit must land in the trash
        block, never in the slot's own blocks."""
        m, params, _, _ = model
        cfg = m.config
        cache = PagedKVCache(cfg, num_blocks=8, block_size=4)
        pool = cache.init_pool()
        bt = np.full((1, 2), TRASH_BLOCK, np.int32)
        bt[0, 0] = 2
        before = np.asarray(pool["k"][:, 2])
        window = np.asarray([[5, 6, 7]], np.int32)
        _, pool = paged_verify_step(
            cfg, params, pool, jnp.asarray(bt),
            jnp.asarray([1], np.int32), jnp.asarray(window),
            jnp.asarray([2], np.int32),  # only position 1 writable
        )
        after = np.asarray(pool["k"][:, 2])
        assert not np.allclose(after[:, 1], before[:, 1])  # pos 1 written
        np.testing.assert_array_equal(after[:, 2:], before[:, 2:])


# ---------------------------------------------------------------------------
# Shape-static top-k sampling (satellite) vs a host reference
# ---------------------------------------------------------------------------

class TestTopK:
    def _host_topk_mask(self, logits, k):
        if k <= 0:
            return logits
        kth = np.sort(logits)[::-1][k - 1]
        return np.where(logits < kth, -1e30, logits)

    def test_topk_masks_match_host_reference(self):
        rng = np.random.default_rng(0)
        logits = rng.normal(size=(4, 32)).astype(np.float32)
        top_ks = np.asarray([0, 1, 5, 32], np.int32)
        temps = np.full((4,), 1.0, np.float32)
        keys = make_slot_keys(
            jax.random.PRNGKey(0), jnp.arange(4), jnp.zeros(4, jnp.int32)
        )
        # Same keys, hand-masked host logits → identical draws.
        want = sample_tokens(
            jnp.asarray(np.stack([
                self._host_topk_mask(row, int(k))
                for row, k in zip(logits, top_ks)
            ])), keys, jnp.asarray(temps),
        )
        got = sample_tokens(
            jnp.asarray(logits), keys, jnp.asarray(temps),
            jnp.asarray(top_ks),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_topk_one_is_greedy(self):
        rng = np.random.default_rng(1)
        logits = rng.normal(size=(3, 16)).astype(np.float32)
        keys = make_slot_keys(
            jax.random.PRNGKey(7), jnp.arange(3), jnp.arange(3)
        )
        got = sample_tokens(
            jnp.asarray(logits), keys,
            jnp.full((3,), 2.0, jnp.float32),
            jnp.ones((3,), jnp.int32),
        )
        np.testing.assert_array_equal(
            np.asarray(got), logits.argmax(-1)
        )

    def test_greedy_rows_ignore_topk_and_keys(self):
        rng = np.random.default_rng(2)
        logits = rng.normal(size=(2, 16)).astype(np.float32)
        for seed in (0, 1):
            keys = make_slot_keys(
                jax.random.PRNGKey(seed), jnp.arange(2), jnp.arange(2)
            )
            got = sample_tokens(
                jnp.asarray(logits), keys,
                jnp.zeros((2,), jnp.float32),
                jnp.asarray([3, 0], jnp.int32),
            )
            np.testing.assert_array_equal(
                np.asarray(got), logits.argmax(-1)
            )

    def test_engine_accepts_topk_requests(self, model):
        m, params, draft, dparams = model
        prompt = _rand_prompt(3, 6)
        # The sampling stream is (engine seed, submit ordinal,
        # position)-keyed: fresh engines replay the same request
        # sequence identically.
        outs = [
            _spec_engine(m, params, draft, dparams, seed=3).generate(
                prompt, 8, temperature=1.0, top_k=4
            )
            for _ in range(2)
        ]
        assert outs[0] == outs[1]
        eng = _spec_engine(m, params, draft, dparams, seed=3)
        with pytest.raises(ValueError, match="top_k"):
            eng.submit(prompt, 4, top_k=0)
        with pytest.raises(ValueError, match="temperature"):
            eng.submit(prompt, 4, top_k=4)


# ---------------------------------------------------------------------------
# Engine acceptance: lossless speculation
# ---------------------------------------------------------------------------

class TestSpecEngine:
    def test_greedy_spec_matches_generate_and_plain_engine(self, model):
        """The lossless-speculation guarantee: spec greedy == non-spec
        greedy == static generate(), token for token."""
        m, params, draft, dparams = model
        spec = _spec_engine(m, params, draft, dparams)
        plain = ServeEngine(m, params,
                            ServeConfig(num_slots=3, block_size=8))
        for seed, n in ((4, 12), (6, 16)):
            prompt = _rand_prompt(seed, 3 + seed)
            want = _ref_tokens(m, params, prompt, n)
            assert spec.generate(prompt, n) == want
            assert plain.generate(prompt, n) == want
        counters = spec.snapshot()["counters"]
        assert counters["spec_ticks"] > 0
        assert counters["spec_drafted"] > 0
        assert counters["spec_accepted"] <= counters["spec_drafted"]

    @pytest.mark.slow  # one verify/chain compile per K (~13s total);
    # the K=3 parity pin above runs in tier-1
    def test_spec_k_sweep_all_lossless(self, model):
        m, params, draft, dparams = model
        prompt = _rand_prompt(7, 5)
        want = _ref_tokens(m, params, prompt, 14)
        for k in (1, 2, 4, 8):
            eng = _spec_engine(m, params, draft, dparams, spec_k=k)
            assert eng.generate(prompt, 14) == want, f"spec_k={k}"

    def test_per_request_spec_zero_rides_along(self, model):
        """spec=0 requests batched WITH speculating requests take the
        verify program's width-1 lane and still match the reference."""
        m, params, draft, dparams = model
        eng = _spec_engine(m, params, draft, dparams)
        p1, p2 = _rand_prompt(8, 6), _rand_prompt(9, 9)
        h1 = eng.submit(p1, 12, spec=0)
        h2 = eng.submit(p2, 12)
        eng.run_until_idle()
        assert h1.result(5) == _ref_tokens(m, params, p1, 12)
        assert h2.result(5) == _ref_tokens(m, params, p2, 12)

    def test_spec_zero_only_traffic_uses_decode_fallback(self, model):
        """An all-spec=0 tick must dispatch the plain decode program
        (decode_steps advances, verify does not)."""
        m, params, draft, dparams = model
        eng = _spec_engine(m, params, draft, dparams)
        prompt = _rand_prompt(10, 4)
        assert eng.generate(prompt, 6, spec=0) == _ref_tokens(
            m, params, prompt, 6
        )
        counters = eng.snapshot()["counters"]
        assert counters["decode_steps"] > 0
        assert counters.get("verify_steps", 0) == 0

    def test_identity_tail_pair_accepts_everything(self, model):
        """Draft + identity-tail target: target logits == draft logits,
        so every draft is accepted and ticks emit K+1 tokens."""
        m, params, draft, dparams = model
        del m, params
        target, tparams = pad_identity_layers(draft, dparams, 3)
        eng = ServeEngine(
            target, tparams, ServeConfig(num_slots=2, block_size=8,
                                         spec_k=3),
            draft_module=draft, draft_params=dparams,
        )
        prompt = _rand_prompt(11, 5)
        got = eng.generate(prompt, 13)
        assert got == _ref_tokens(target, tparams, prompt, 13)
        snap = eng.snapshot()
        assert snap["gauges"]["spec_acceptance_rate"] == 1.0

    def test_eos_inside_accepted_window_stops_exactly(self, model):
        """An eos token landing mid-window truncates the emission at
        eos (inclusive) — no token after it leaks out, and the caches
        roll back to the real frontier."""
        m, params, draft, dparams = model
        prompt = _rand_prompt(12, 5)
        ref = _ref_tokens(m, params, prompt, 10)
        eos = ref[4]
        eng = _spec_engine(m, params, draft, dparams)
        h = eng.submit(prompt, 10, eos_token_id=eos)
        eng.run_until_idle()
        assert h.result(5) == ref[: ref.index(eos) + 1]
        assert h.request.done_reason == "eos"
        assert eng.snapshot()["gauges"]["blocks_free"] == float(
            eng.cache.num_blocks - 1
        )

    def test_join_on_arrival_under_spec(self, model):
        m, params, draft, dparams = model
        eng = _spec_engine(m, params, draft, dparams, num_slots=4)
        p1, p2 = _rand_prompt(13, 6), _rand_prompt(14, 11)
        h1 = eng.submit(p1, 12)
        for _ in range(2):
            eng.step()
        h2 = eng.submit(p2, 8)
        eng.run_until_idle()
        assert h1.result(5) == _ref_tokens(m, params, p1, 12)
        assert h2.result(5) == _ref_tokens(m, params, p2, 8)

    def test_preemption_under_block_exhaustion_with_spec(self, model):
        """Speculative coverage claims more blocks per tick; preemption
        under exhaustion must still produce reference tokens for both
        requests and return every block."""
        m, params, draft, dparams = model
        # 7 usable blocks vs two sequences needing 5 each: baseline
        # growth must preempt (the spec windows only shrink).
        eng = _spec_engine(
            m, params, draft, dparams,
            num_slots=2, block_size=4, num_blocks=8, max_model_len=24,
        )
        p1, p2 = [3, 1, 4, 1], [2, 7, 1]
        h1, h2 = eng.submit(p1, 16), eng.submit(p2, 16)
        eng.run_until_idle()
        assert h1.result(5) == _ref_tokens(m, params, p1, 16)
        assert h2.result(5) == _ref_tokens(m, params, p2, 16)
        snap = eng.snapshot()
        assert snap["counters"]["preempted"] >= 1
        assert snap["gauges"]["blocks_free"] == 7.0

    def test_spec_coverage_never_preempts_and_terminates(self, model):
        """Regression (round-16 verify): speculative window coverage is
        OPPORTUNISTIC.  Two temperature>0 requests on a pool that can't
        fund both verify windows used to preempt each other's windows
        in a ping-pong that never made forward progress; now a dry pool
        shrinks the tick's draft width instead, preemption stays
        baseline-only, and both requests finish."""
        m, params, draft, dparams = model
        eng = _spec_engine(
            m, params, draft, dparams,
            num_slots=2, block_size=4, num_blocks=8, max_model_len=24,
            seed=11,
        )
        h1 = eng.submit([3, 1, 4, 1], 16, temperature=1.0)
        h2 = eng.submit([2, 7, 1], 16, temperature=0.8, top_k=8)
        eng.run_until_idle(max_steps=4000)  # livelock = loud failure
        assert len(h1.result(5)) == 16 and len(h2.result(5)) == 16
        assert eng.snapshot()["gauges"]["blocks_free"] == 7.0

    def test_fallback_ticks_keep_draft_cache_synced(self, model):
        """Regression (round-16 review): a decode-fallback tick on a
        speculative engine (pool pressure shrank every window to zero)
        must mirror its write into the DRAFT cache — with the
        identity-tail pair any stale draft position shows up as
        acceptance < 1.0 on later ticks."""
        m, params, draft, dparams = model
        del m, params
        target, tparams = pad_identity_layers(draft, dparams, 3)
        eng = ServeEngine(
            target, tparams,
            ServeConfig(num_slots=1, block_size=4, spec_k=3),
            draft_module=draft, draft_params=dparams,
        )
        p = [3, 1, 4]  # seq 3 → first spec tick lands on 7 (mid-block)
        h = eng.submit(p, 12)
        eng.step()  # prefill + full-width spec tick: seq_len 3 → 7
        assert int(eng.scheduler.seq_lens[0]) == 7
        # Dry pool at a frontier whose NEXT position is still covered:
        # every window width fails cover, baseline doesn't need a
        # block — the tick must fall back to plain decode.
        alloc = eng.cache.allocator
        hog = alloc.alloc(alloc.free_blocks)
        before = eng.snapshot()["counters"].get("decode_steps", 0)
        eng.step()
        assert eng.snapshot()["counters"]["decode_steps"] == before + 1
        assert int(eng.scheduler.seq_lens[0]) == 8
        # The frontier claim must be BACKED by a real write: position 7
        # (block 1, offset 3) of the DRAFT pool carries the fallback
        # token's k/v, not the pool's zero-fill (the discriminating
        # probe — a zero/stale row only degrades acceptance softly).
        assert int(eng.scheduler.draft_lens[0]) == 8
        blk = eng.scheduler._blocks[0][1]
        assert np.any(np.asarray(eng._draft_pool["k"][:, blk, 3]) != 0.0)
        # Pool returns; speculation resumes conditioned on the
        # fallback-written position.
        alloc.free(hog)
        eng.run_until_idle(max_steps=4000)
        assert h.result(5) == _ref_tokens(target, tparams, p, 12)
        snap = eng.snapshot()
        assert snap["counters"]["spec_drafted"] > 0
        # The draft never proposed from a stale cache.
        assert snap["gauges"]["spec_acceptance_rate"] == 1.0

    def test_steady_state_zero_recompiles_with_spec(self, model):
        """The program-set contract: draft prefill/step, verify, decode
        fallback and the bucketed target prefills compile during
        warmup; steady-state speculative traffic compiles NOTHING."""
        m, params, draft, dparams = model
        eng = _spec_engine(m, params, draft, dparams)
        eng.generate(_rand_prompt(15, 5), 4)            # bucket 8
        eng.generate(_rand_prompt(16, 12), 4)           # bucket 16
        eng.generate(_rand_prompt(17, 4), 3, spec=0)    # decode fallback
        from ray_lightning_tpu.serve.metrics import ServeStats

        eng.stats = ServeStats()  # count steady-state traffic only
        before = compile_event_count()
        for seed in range(8):
            eng.submit(
                _rand_prompt(20 + seed, 3 + (seed % 12)),
                3 + seed % 6, spec=0 if seed % 4 == 0 else None,
            )
        eng.run_until_idle()
        assert eng.snapshot()["counters"]["completed"] == 8
        assert compile_event_count() - before == 0

    def test_draftless_engine_rejects_spec_and_spec_knob_validates(
            self, model):
        m, params, draft, dparams = model
        plain = ServeEngine(m, params,
                            ServeConfig(num_slots=1, block_size=8))
        with pytest.raises(ValueError, match="draft"):
            plain.submit([1, 2], 4, spec=2)
        with pytest.raises(ValueError, match="spec_k"):
            ServeEngine(m, params,
                        ServeConfig(num_slots=1, block_size=8, spec_k=2))
        with pytest.raises(ValueError, match="spec_k"):
            ServeEngine(m, params,
                        ServeConfig(num_slots=1, block_size=8),
                        draft_module=draft, draft_params=dparams)
        with pytest.raises(ValueError, match="vocab"):
            other = GPT(GPTConfig(vocab_size=64, n_layer=2, n_head=4,
                                  d_model=64, seq_len=64,
                                  warmup_steps=1), attn_impl="xla")
            ServeEngine(
                m, params,
                ServeConfig(num_slots=1, block_size=8, spec_k=2),
                draft_module=other,
                draft_params=other.init_params(jax.random.PRNGKey(1)),
            )


# ---------------------------------------------------------------------------
# Temperature reproducibility across recompute preemption (satellite):
# the rollback path's load-bearing contract beyond greedy.
# ---------------------------------------------------------------------------

class TestSamplingReproducibility:
    def _run_with_preemption(self, m, params, draft, dparams, spec_k):
        emissions = {}

        def on_token(rid):
            def cb(i, t):
                emissions.setdefault(rid, []).append((i, t))
            return cb

        kw = dict(num_slots=2, block_size=4, num_blocks=10,
                  max_model_len=24, seed=7)
        if spec_k:
            eng = _spec_engine(m, params, draft, dparams,
                               spec_k=spec_k, **kw)
        else:
            eng = ServeEngine(m, params, ServeConfig(**kw))
        h1 = eng.submit([3, 1, 4, 1], 16, temperature=1.0,
                        on_token=on_token("a"))
        h2 = eng.submit([2, 7, 1], 16, temperature=0.8,
                        on_token=on_token("b"))
        eng.run_until_idle()
        assert eng.snapshot()["counters"]["preempted"] >= 1
        return emissions, h1.result(5), h2.result(5)

    @pytest.mark.parametrize("spec_k", [0, 3])
    def test_reemitted_tokens_bitwise_equal(self, model, spec_k):
        """After a recompute preemption the re-decode replays the SAME
        per-position sampling keys: every re-emitted index carries the
        token of the first emission, at temperature > 0."""
        m, params, draft, dparams = model
        emissions, r1, r2 = self._run_with_preemption(
            m, params, draft, dparams, spec_k
        )
        reemitted = 0
        for rid, ems in emissions.items():
            seen = {}
            for i, t in ems:
                if i in seen:
                    reemitted += 1
                    assert seen[i] == t, (
                        f"request {rid} re-emitted index {i} as {t}, "
                        f"first emission was {seen[i]}"
                    )
                seen[i] = t
            # The final result is exactly the deduped stream.
            assert [seen[i] for i in range(len(seen))] in (r1, r2)
        assert reemitted > 0, "no preemption re-emission exercised"

    def test_fresh_engine_reproduces_preempted_run(self, model):
        """Same seed, no preemption pressure → identical outputs: the
        preempted run lost nothing to the rollback."""
        m, params, draft, dparams = model
        _, r1, r2 = self._run_with_preemption(
            m, params, draft, dparams, spec_k=3
        )
        calm = _spec_engine(m, params, draft, dparams, spec_k=3,
                            num_slots=2, block_size=4, seed=7)
        g1 = calm.submit([3, 1, 4, 1], 16, temperature=1.0)
        g2 = calm.submit([2, 7, 1], 16, temperature=0.8)
        calm.run_until_idle()
        assert calm.snapshot()["counters"]["preempted"] == 0
        assert g1.result(5) == r1
        assert g2.result(5) == r2

    def test_temperature_stream_slot_independent(self, model):
        """A request's sampled tokens must not depend on which slot it
        lands in or who shares the batch (the property that makes
        preemption rollback safe)."""
        m, params, draft, dparams = model
        prompt = _rand_prompt(18, 5)
        alone = _spec_engine(m, params, draft, dparams, seed=5)
        want = alone.generate(prompt, 8, temperature=0.9)
        # Same submit ordinal (first), but now two neighbours share the
        # batch: the probe's tokens must not move.
        crowded = _spec_engine(m, params, draft, dparams, seed=5,
                               num_slots=3)
        h = crowded.submit(prompt, 8, temperature=0.9)
        others = [crowded.submit(_rand_prompt(19 + i, 4 + i), 8,
                                 temperature=1.3) for i in range(2)]
        crowded.run_until_idle()
        for o in others:
            o.result(5)
        assert h.result(5) == want


# ---------------------------------------------------------------------------
# Client plane under variable-width emission (satellite)
# ---------------------------------------------------------------------------

class TestClientVariableWidth:
    def test_stream_dedup_under_spec_and_preemption(self, model):
        """Index-based dedup holds when tokens arrive in multi-token
        bursts and re-emissions cross burst boundaries."""
        m, params, draft, dparams = model
        # 7 usable blocks, two 20-token sequences needing 5 each plus
        # speculative coverage: exhaustion (hence preemption and
        # re-emission) is guaranteed while both are in flight.
        eng = _spec_engine(
            m, params, draft, dparams,
            num_slots=2, block_size=4, num_blocks=8, max_model_len=24,
        )
        client = ServeClient(eng.queue_handle())
        try:
            p1, p2 = [3, 1, 4, 1], [2, 7, 1]
            r2 = client.submit(p2, 16)
            stream = client.stream(p1, 16, timeout=60)
            eng.start()  # engine thread drives while the stream consumes
            toks = list(stream)
            assert toks == _ref_tokens(m, params, p1, 16)
            assert client.result(r2, 30) == _ref_tokens(m, params, p2, 16)
            assert eng.snapshot()["counters"]["preempted"] >= 1
        finally:
            eng.stop()
            client.close()

    def test_client_spec_and_topk_fields_roundtrip(self, model):
        from ray_lightning_tpu.telemetry.schema import (
            validate_serve_request,
        )

        m, params, draft, dparams = model
        eng = _spec_engine(m, params, draft, dparams)
        seen = []
        orig = eng._handle_queue_request

        def spy(item, *recv_t):
            seen.append(item)
            orig(item, *recv_t)

        eng._handle_queue_request = spy
        client = ServeClient(eng.queue_handle())
        try:
            eng.start()
            prompt = _rand_prompt(20, 5)
            got = client.generate(prompt, 6, temperature=1.0, top_k=5,
                                  spec=2, timeout=60)
            assert len(got) == 6
            assert seen and seen[0]["top_k"] == 5 and seen[0]["spec"] == 2
            assert validate_serve_request(seen[0]) == []
            # spec=0 over the wire → plain decode, reference tokens.
            want = _ref_tokens(m, params, prompt, 6)
            assert client.generate(prompt, 6, spec=0, timeout=60) == want
        finally:
            eng.stop()
            client.close()


# ---------------------------------------------------------------------------
# Telemetry: snapshot schema, prom family
# ---------------------------------------------------------------------------

class TestSpecTelemetry:
    def test_snapshot_schema_and_prom_family(self, model):
        from ray_lightning_tpu.telemetry.export_prom import (
            render_openmetrics,
        )
        from ray_lightning_tpu.telemetry.schema import (
            validate_serve_snapshot,
        )

        m, params, draft, dparams = model
        eng = _spec_engine(m, params, draft, dparams)
        eng.generate(_rand_prompt(21, 5), 8)
        snap = eng.snapshot()
        assert validate_serve_snapshot(snap) == []
        assert 0.0 <= snap["gauges"]["spec_acceptance_rate"] <= 1.0
        # 8 new tokens = 1 from prefill + 7 speculative.
        assert snap["counters"]["spec_emitted"] == 7
        text = render_openmetrics({"serve": snap})
        assert 'rlt_serve_spec_tokens_total{kind="drafted"}' in text
        assert 'rlt_serve_spec_tokens_total{kind="accepted"}' in text
        assert "rlt_serve_spec_acceptance_rate" in text
        assert "rlt_serve_spec_goodput_tokens_per_sec" in text
        # Spec token counters stay OUT of the generic request family.
        assert 'rlt_serve_requests_total{kind="spec_drafted"}' not in text

    def test_rlt_top_shows_acceptance(self, model, tmp_path):
        m, params, draft, dparams = model
        eng = ServeEngine(
            m, params,
            ServeConfig(num_slots=2, block_size=8, spec_k=3,
                        export_every_s=0.0),
            telemetry_dir=str(tmp_path),
            draft_module=draft, draft_params=dparams,
        )
        eng.generate(_rand_prompt(22, 5), 6)
        assert (tmp_path / "serve-live.json").exists()
        out = rlt_top_once(tmp_path)
        assert "spec acc" in out.stdout
