"""Serving plane: paged KV cache vs the static path, continuous batching.

The correctness contract mirrors the repo's grad-parity discipline:
``models/generate.py`` (the static one-cache-per-batch path) is the
reference — a request served through the paged cache must produce
exactly the tokens ``generate()`` would, regardless of what else is in
flight, which blocks it landed on, or how many times it was preempted.
On top: block free/reuse correctness, the zero-recompile steady-state
guarantee (via the telemetry recompile counter), scheduler policy
units, SLO stats schema, and the DriverQueue client plane.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.generate import generate
from ray_lightning_tpu.ops import paged_attention
from ray_lightning_tpu.serve.client import ServeClient
from ray_lightning_tpu.serve.engine import (
    ServeConfig, ServeEngine, ServeRejected,
)
from ray_lightning_tpu.serve.kv_cache import (
    TRASH_BLOCK, BlockAllocator, PagedKVCache, paged_decode_step,
    paged_prefill,
)
from ray_lightning_tpu.serve.metrics import ServeStats, percentile
from ray_lightning_tpu.serve.scheduler import (
    Request, Scheduler, default_buckets,
)
from ray_lightning_tpu.telemetry import compile_event_count

from utils import rand_prompt as _rand_prompt
from utils import reference_tokens as _ref_tokens
from utils import rlt_top_once, tiny_gpt

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def model():
    return tiny_gpt()


# ---------------------------------------------------------------------------
# Block allocator + scheduler policy (jax-free units)
# ---------------------------------------------------------------------------

class TestAllocator:
    def test_alloc_free_reuse(self):
        a = BlockAllocator(6)
        assert a.free_blocks == 5  # block 0 reserved
        ids = a.alloc(3)
        assert len(ids) == 3 and TRASH_BLOCK not in ids
        assert a.alloc(3) is None          # all-or-nothing
        assert a.free_blocks == 2
        a.free(ids)
        assert a.free_blocks == 5
        again = a.alloc(5)
        assert sorted(again) == [1, 2, 3, 4, 5]

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        ids = a.alloc(1)
        a.free(ids)
        with pytest.raises(RuntimeError, match="double-free"):
            a.free(ids)
        with pytest.raises(RuntimeError, match="not live"):
            a.free([2])

    def test_too_small_pool_rejected(self):
        with pytest.raises(ValueError, match="reserved"):
            BlockAllocator(1)


class TestSchedulerPolicy:
    def _sched(self, num_slots=2, num_blocks=9, max_queue=4):
        alloc = BlockAllocator(num_blocks)
        return Scheduler(num_slots, alloc, block_size=4,
                         max_blocks_per_seq=4, buckets=[4, 8, 16],
                         max_queue=max_queue)

    def _req(self, rid, prompt_len=3, max_new=4, **kw):
        return Request(rid=rid, prompt=list(range(1, prompt_len + 1)),
                       max_new_tokens=max_new, **kw)

    def test_default_buckets_cover_max_prompt(self):
        assert default_buckets(16, 100) == [16, 32, 64, 128]
        assert default_buckets(8, 8) == [8]

    def test_bucket_for_picks_smallest_cover(self):
        s = self._sched()
        assert s.bucket_for(3) == 4
        assert s.bucket_for(4) == 4
        assert s.bucket_for(5) == 8
        with pytest.raises(ValueError, match="exceeds"):
            s.bucket_for(17)

    def test_admission_fifo_and_slot_fill(self):
        s = self._sched()
        for i in range(3):
            assert s.submit(self._req(f"r{i}"))
        admissions, expired = s.poll(now=0.0)
        assert not expired
        assert [r.rid for _, r, _ in admissions] == ["r0", "r1"]
        assert s.queue_depth == 1 and s.active_slots == 2
        # Slot rows populated for the compiled step.
        for slot, req, bucket in admissions:
            assert bucket == 4
            assert s.seq_lens[slot] == req.prompt_len
            assert s.block_tables[slot, 0] != TRASH_BLOCK

    def test_backpressure_rejects_beyond_max_queue(self):
        s = self._sched(max_queue=2)
        assert s.submit(self._req("a")) and s.submit(self._req("b"))
        rej = self._req("c")
        assert not s.submit(rej)
        assert rej.done_reason == "rejected"

    def test_deadline_expires_queued_requests(self):
        s = self._sched()
        req = self._req("late", deadline_s=0.5)
        req.arrival_t = 100.0
        s.submit(req)
        admissions, expired = s.poll(now=101.0)
        assert not admissions and [r.rid for r in expired] == ["late"]
        assert req.done_reason == "expired"

    def test_growth_and_preemption_frees_youngest(self):
        # Pool of 8 usable blocks, two admitted sequences (1 block
        # each); exhaust the rest, then growth must preempt the
        # YOUNGER request and requeue it at the front.
        s = self._sched(num_blocks=9)
        s.submit(self._req("old", prompt_len=4))
        s.submit(self._req("young", prompt_len=4))
        (s0, old, _), (s1, young, _) = s.poll(now=0.0)[0]
        hog = s.allocator.alloc(6)
        s.seq_lens[s0] += 4  # next write crosses into block 2
        assert s.needs_block(s0) and not s.grow(s0)
        victim = s.preempt_youngest(protect=s0)
        assert victim is young and victim.preemptions == 1
        assert s.queue[0].rid == "young"
        s.allocator.free(hog)
        assert s.grow(s0)
        # The freed slot is admissible again.
        admissions, _ = s.poll(now=1.0)
        assert [r.rid for _, r, _ in admissions] == ["young"]

    def test_finish_releases_everything(self):
        s = self._sched()
        s.submit(self._req("a"))
        (slot, req, _), = s.poll(now=0.0)[0]
        free_before = s.allocator.free_blocks
        s.append_token(slot, 7, now=0.1)
        done = s.finish(slot, now=0.2)
        assert done.state.value == "finished"
        assert s.slots[slot] is None
        assert (s.block_tables[slot] == TRASH_BLOCK).all()
        assert s.allocator.free_blocks == free_before + 1

    def test_preempted_request_survives_deadline_on_requeue(self):
        """deadline_s is a TTFT-at-admission SLO: a request that already
        streamed tokens and was preempted back into the queue must NOT
        be expired on re-admission, however late it is."""
        s = self._sched()
        req = self._req("a", deadline_s=0.5)
        req.arrival_t = 100.0
        s.submit(req)
        (slot, r, _), = s.poll(now=100.1)[0]
        s.append_token(slot, 7, now=100.2)  # first token delivered
        assert s.preempt_youngest() is req
        admissions, expired = s.poll(now=200.0)  # way past the deadline
        assert not expired
        assert [x.rid for _, x, _ in admissions] == ["a"]

    def test_raising_on_token_does_not_break_append(self):
        s = self._sched()

        def bad(i, t):
            raise RuntimeError("consumer bug")

        s.submit(self._req("a", on_token=bad, max_new=1))
        (slot, req, _), = s.poll(now=0.0)[0]
        assert s.append_token(slot, 5) is True
        assert req.generated == [5]


# ---------------------------------------------------------------------------
# Paged cache vs the static path (device programs)
# ---------------------------------------------------------------------------

class TestPagedParity:
    def test_prefill_logits_match_full_forward(self, model):
        """A padded-bucket prefill == the full forward's logits at the
        last VALID prompt position, and the written blocks hold exactly
        the contiguous cache's k/v."""
        m, params = model
        cfg = m.config
        toks = _rand_prompt(1, 5, cfg.vocab_size)
        full = np.asarray(m.forward(params, jnp.asarray([toks])))
        cache = PagedKVCache(cfg, num_blocks=8, block_size=8)
        pool = cache.init_pool()
        ids = cache.allocator.alloc(1)
        padded = np.zeros((8,), np.int32)
        padded[:5] = toks
        logits, pool = paged_prefill(
            cfg, params, pool, jnp.asarray(padded), jnp.int32(5),
            jnp.asarray(np.asarray(ids, np.int32)),
        )
        np.testing.assert_allclose(
            np.asarray(logits), full[0, 4], rtol=1e-4, atol=1e-4
        )
        # Cache content parity against the static path.
        from ray_lightning_tpu.models.generate import init_kv_cache, prefill
        ref_cache = init_kv_cache(cfg, 1, 8)
        _, ref_cache = prefill(cfg, params, ref_cache,
                               jnp.asarray(padded[None, :5]))
        got_k = np.asarray(pool["k"][:, ids[0], :5])
        # A pool row holds the position's heads side by side.
        want_k = np.asarray(ref_cache["k"][:, 0, :5])
        np.testing.assert_allclose(
            got_k, want_k.reshape(got_k.shape), rtol=1e-5, atol=1e-5,
        )

    @pytest.mark.slow  # tier-1 diet (round 20): ~11s token-by-token
    # sweep; prefill_logits parity is the tier-1 paged-parity smoke
    def test_teacher_forced_decode_matches_full_forward(self, model):
        """Feeding tokens one-by-one through the PAGED cache reproduces
        the full forward's logits at every position — across block
        boundaries and with the sequence's blocks deliberately
        scattered through the pool."""
        m, params = model
        cfg = m.config
        toks = np.asarray(_rand_prompt(2, 15, cfg.vocab_size))
        full = np.asarray(m.forward(params, jnp.asarray([toks])))
        cache = PagedKVCache(cfg, num_blocks=16, block_size=4)
        pool = cache.init_pool()
        # Non-contiguous physical placement: logical block i lands on
        # physical block 2i+1.
        phys = [1, 3, 5, 7]
        bt = np.full((2, 4), TRASH_BLOCK, np.int32)
        seq_lens = np.zeros((2,), np.int32)
        for t in range(15):
            if t % 4 == 0:
                bt[0, t // 4] = phys[t // 4]
            logits, pool = paged_decode_step(
                cfg, params, pool, jnp.asarray(bt),
                jnp.asarray(seq_lens),
                jnp.asarray(np.array([toks[t], 0], np.int32)),
            )
            np.testing.assert_allclose(
                np.asarray(logits)[0], full[0, t], rtol=1e-4, atol=1e-4
            )
            seq_lens[0] += 1


# ---------------------------------------------------------------------------
# Engine acceptance: continuous batching == isolated static decoding
# ---------------------------------------------------------------------------

class TestEngine:
    def test_single_request_matches_generate(self, model):
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=2,
                                                 block_size=8))
        prompt = _rand_prompt(3, 7, m.config.vocab_size)
        assert eng.generate(prompt, 9) == _ref_tokens(m, params, prompt, 9)

    @pytest.mark.parametrize("kernel", [True, False])
    def test_walk_counters_are_the_walk_plan(self, monkeypatch, kernel):
        """``decode_attn_steps`` / ``decode_attn_positions_computed``:
        every tick's ``walk_plan`` of the active slots' lengths, times
        the layers, where the decode runs the kernel (here under the
        interpreter), and absent where it takes the XLA gather.  One
        request at a time, so a request of prompt ``n`` decodes at
        lengths ``n .. n + k - 2``: a short one, one across the edge of
        the walk's width, one past two whole steps."""
        from ray_lightning_tpu.models.gpt import GPT, GPTConfig
        if kernel:
            monkeypatch.setattr(paged_attention, "paged_decode_supported",
                                paged_attention.paged_decode_tiles)
        wide = paged_attention.CHUNK_POSITIONS
        S = 2 * wide + 256
        # Rows of 128 lanes: a pool the kernel tiles.
        m = GPT(GPTConfig(vocab_size=128, n_layer=2, n_head=2, d_model=128,
                          seq_len=S, warmup_steps=1), attn_impl="xla")
        params = m.init_params(jax.random.PRNGKey(0))
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=2, block_size=16, prefill_buckets=[64, S]))
        M = S // 16
        steps = positions = ticks = held = 0
        for n in (5, wide - 2, 2 * wide + 100):
            eng.generate(_rand_prompt(n, n, m.config.vocab_size), 5)
            for length in range(n, n + 4):
                s, p = paged_attention.walk_plan([length], M, 16)
                steps, positions = steps + s, positions + p
                ticks, held = ticks + 1, held + length
        c = eng.stats.counters
        eng.stop()
        assert c["decode_steps"] == ticks
        if not kernel:
            assert not c.get("decode_attn_steps")
            assert not c.get("decode_attn_positions_computed")
            return
        assert c["decode_attn_steps"] == m.config.n_layer * steps
        assert c["decode_attn_positions_computed"] == (
            m.config.n_layer * positions)
        # Under a sub-tile of masked positions a slot a layer.
        assert held <= positions < held + 128 * ticks

    def test_join_on_arrival_matches_isolated(self, model):
        """A request admitted MID-decode of another must not disturb
        either: both match their isolated static-path rollouts."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=4,
                                                 block_size=8))
        p1 = _rand_prompt(4, 6, m.config.vocab_size)
        p2 = _rand_prompt(5, 11, m.config.vocab_size)
        h1 = eng.submit(p1, 12)
        for _ in range(4):  # p1 alone for a few decode steps
            eng.step()
        h2 = eng.submit(p2, 8)  # joins the running batch
        eng.run_until_idle()
        assert h1.result(5) == _ref_tokens(m, params, p1, 12)
        assert h2.result(5) == _ref_tokens(m, params, p2, 8)
        assert eng.snapshot()["counters"]["completed"] == 2

    @pytest.mark.slow  # tier-1 diet (round 20): ~8s multi-wave fit;
    # join_on_arrival + preemption keep block reuse covered in tier-1
    def test_block_free_and_reuse_is_clean(self, model):
        """After a request finishes its blocks are reused by the next
        admission — stale cache content leaking through would corrupt
        the successor's tokens."""
        m, params = model
        # 5 usable blocks: a full max_model_len sequence needs 4, so
        # consecutive requests MUST reuse each other's blocks.
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=1, block_size=8, num_blocks=6, max_model_len=32,
        ))
        for seed in (6, 7, 8):
            prompt = _rand_prompt(seed, 9, m.config.vocab_size)
            assert eng.generate(prompt, 12) == _ref_tokens(
                m, params, prompt, 12
            )
        snap = eng.snapshot()
        assert snap["gauges"]["blocks_free"] == 5.0
        assert snap["counters"]["completed"] == 3

    def test_steady_state_triggers_zero_recompiles(self, model):
        """The acceptance bar: after warmup, join-on-arrival traffic of
        mixed prompt lengths (same buckets) and evict-on-finish churn
        must not trigger a single XLA compile (telemetry counter)."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=3,
                                                 block_size=8))
        # Warmup: one request per bucket the traffic will use.
        eng.generate(_rand_prompt(9, 5, m.config.vocab_size), 4)   # b=8
        eng.generate(_rand_prompt(10, 12, m.config.vocab_size), 4)  # b=16
        eng.stats = ServeStats()  # count steady-state traffic only
        before = compile_event_count()
        for seed in range(8):
            eng.submit(
                _rand_prompt(20 + seed, 3 + (seed % 12), 128),
                3 + seed % 5,
            )
        eng.run_until_idle()
        assert eng.snapshot()["counters"]["completed"] == 8
        assert compile_event_count() - before == 0

    def test_preemption_under_block_exhaustion(self, model):
        """Pool too small for two full sequences: the younger request
        is preempted (recompute) and BOTH still match the static path
        bitwise."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=2, block_size=4, num_blocks=8, max_model_len=24,
        ))
        p1, p2 = [3, 1, 4, 1], [2, 7, 1]
        h1 = eng.submit(p1, 16)
        h2 = eng.submit(p2, 16)
        eng.run_until_idle()
        assert h1.result(5) == _ref_tokens(m, params, p1, 16)
        assert h2.result(5) == _ref_tokens(m, params, p2, 16)
        snap = eng.snapshot()
        assert snap["counters"]["preempted"] >= 1
        assert snap["gauges"]["blocks_free"] == 7.0  # all returned

    def test_backpressure_and_deadline(self, model):
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=1, block_size=8, max_queue=2,
        ))
        a = eng.submit([1, 2, 3], 4)
        b = eng.submit([4, 5], 4)
        c = eng.submit([6], 4)  # queue full → rejected synchronously
        assert c.status == "rejected"
        with pytest.raises(ServeRejected, match="rejected"):
            c.result(1)
        # Deadline: admit a first (freeing a queue seat), then a
        # zero-deadline request expires while queued behind b.
        eng.step()
        d = eng.submit([7, 8], 4, deadline_s=0.0)
        time.sleep(0.01)
        eng.run_until_idle()
        assert a.result(5) and b.result(5)
        with pytest.raises(ServeRejected, match="expired"):
            d.result(1)
        counters = eng.snapshot()["counters"]
        assert counters["rejected"] == 1 and counters["expired"] == 1

    def test_submit_validates(self, model):
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=1,
                                                 block_size=8))
        with pytest.raises(ValueError, match="at least one"):
            eng.submit([], 4)
        with pytest.raises(ValueError, match=">= 1"):
            eng.submit([1], 0)
        with pytest.raises(ValueError, match="max_model_len"):
            eng.submit([1] * 60, 10)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit([m.config.vocab_size], 2)

    def test_prompt_beyond_largest_bucket_is_typed_rejection(self, model):
        """A non-bucket-aligned max_model_len drops the covering
        bucket; prompts past the largest RETAINED bucket must be a
        typed submit() rejection, never a serve-loop crash."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=2, block_size=8, max_model_len=24,
        ))
        assert eng.max_prompt_len == 16  # buckets [8, 16]; 32 dropped
        with pytest.raises(ValueError, match="largest prefill bucket"):
            eng.submit(list(range(1, 18)), 1)  # 17+1 <= 24 alone passes
        assert len(eng.generate([1, 2, 3], 2)) == 2  # loop healthy

    def test_unbucketable_block_size_raises_at_build(self, model):
        m, params = model
        with pytest.raises(ValueError, match="no prefill bucket"):
            ServeEngine(m, params, ServeConfig(
                num_slots=1, block_size=32, max_model_len=16,
            ))

    def test_serve_loop_death_fails_pending_loudly(self, model):
        """An exception escaping step() on the background thread must
        fail every pending handle with the chained error and turn the
        engine dead for new submits — never strand clients at their
        timeouts."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=2,
                                                 block_size=8))

        def boom(*a, **k):
            raise RuntimeError("injected device fault")

        eng._decode_fn = boom
        eng.start()
        try:
            h = eng.submit([1, 2, 3], 4)
            with pytest.raises(RuntimeError, match="engine died"):
                h.result(timeout=30)
            with pytest.raises(RuntimeError, match="dead"):
                eng.submit([1, 2, 3], 4)
        finally:
            eng.stop()

    def test_eos_and_streaming_callback(self, model):
        """eos stops the request early; on_token saw every token in
        order."""
        m, params = model
        prompt = _rand_prompt(11, 5, m.config.vocab_size)
        ref = _ref_tokens(m, params, prompt, 8)
        eos = ref[3]
        seen = []
        eng = ServeEngine(m, params, ServeConfig(num_slots=2,
                                                 block_size=8))
        h = eng.submit(prompt, 8, eos_token_id=eos,
                       on_token=lambda i, t: seen.append((i, t)))
        eng.run_until_idle()
        got = h.result(5)
        # Stopped at the FIRST occurrence of eos in the reference
        # rollout (greedy regenerates the same prefix).
        assert got == ref[: ref.index(eos) + 1]
        assert seen == list(enumerate(got))
        assert h.request.done_reason == "eos"

    def test_temperature_sampling_reproducible(self, model):
        m, params = model
        prompt = _rand_prompt(12, 6, m.config.vocab_size)
        outs = []
        for _ in range(2):
            eng = ServeEngine(m, params, ServeConfig(
                num_slots=2, block_size=8, seed=7,
            ))
            outs.append(eng.generate(prompt, 8, temperature=1.0))
        assert outs[0] == outs[1]

    def test_int8_engine_matches_int8_generate(self, model):
        """The int8-storage tree through the paged path == the static
        path fed the SAME tree (both dequant-hoisted off-TPU)."""
        from ray_lightning_tpu.models.quant import quantize_decode_params

        m, params = model
        q8 = quantize_decode_params(params, m.config)
        prompt = _rand_prompt(13, 6, m.config.vocab_size)
        eng = ServeEngine(m, q8, ServeConfig(num_slots=2, block_size=8))
        ref = generate(m, q8, jnp.asarray([prompt], jnp.int32), 7)
        assert eng.generate(prompt, 7) == np.asarray(ref)[0, 6:].tolist()


# ---------------------------------------------------------------------------
# SLO stats + schema + exporters
# ---------------------------------------------------------------------------

class TestServeStats:
    def test_percentile_nearest_rank(self):
        assert percentile([], 50) is None
        assert percentile([3.0], 99) == 3.0
        vals = [float(i) for i in range(1, 101)]
        assert percentile(vals, 50) == 50.0
        assert percentile(vals, 99) == 99.0
        assert percentile(vals, 0) == 1.0

    def test_snapshot_is_schema_valid(self):
        from ray_lightning_tpu.telemetry.schema import (
            validate_serve_snapshot,
        )

        s = ServeStats()
        s.bump("submitted", 3)
        s.note_admitted(0.01)
        s.note_first_token(0.02)
        s.note_token_latency(0.004, n_tokens=2)
        s.note_completed(0.5)
        s.set_gauges(queue_depth=1, slots_active=1, num_slots=4,
                     blocks_free=3, blocks_live=2, num_blocks=6)
        snap = s.snapshot()
        assert validate_serve_snapshot(snap) == []
        assert snap["counters"]["tokens_out"] == 2
        assert snap["latency"]["token"]["n"] == 2

    def test_engine_snapshot_schema_and_prom_render(self, model):
        from ray_lightning_tpu.telemetry.export_prom import (
            render_openmetrics,
        )
        from ray_lightning_tpu.telemetry.schema import (
            validate_serve_snapshot,
        )

        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=2,
                                                 block_size=8))
        eng.generate([1, 2, 3], 4)
        snap = eng.snapshot()
        assert validate_serve_snapshot(snap) == []
        text = render_openmetrics({"serve": snap})
        assert "rlt_serve_slots_active" in text
        assert 'rlt_serve_requests_total{kind="completed"} 1' in text
        assert 'rlt_serve_token_latency_ms{quantile="p50"}' in text

    def test_rlt_top_renders_serve_live(self, model, tmp_path):
        m, params = model
        eng = ServeEngine(
            m, params,
            ServeConfig(num_slots=2, block_size=8, export_every_s=0.0),
            telemetry_dir=str(tmp_path),
        )
        eng.generate([5, 6], 3)
        assert (tmp_path / "serve-live.json").exists()
        out = rlt_top_once(tmp_path)
        assert "serve:" in out.stdout and "slots" in out.stdout


# ---------------------------------------------------------------------------
# DriverQueue client plane
# ---------------------------------------------------------------------------

class TestClientPlane:
    def test_generate_stream_and_backpressure_over_queue(self, model):
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=1, block_size=8, max_queue=2,
        ))
        client = ServeClient(eng.queue_handle())
        try:
            p1 = _rand_prompt(14, 5, m.config.vocab_size)
            p2 = _rand_prompt(15, 4, m.config.vocab_size)
            r1 = client.submit(p1, 6)
            r2 = client.submit(p2, 5)
            r3 = client.submit([1], 2)   # queue full once drained
            # Engine not started: drain deterministically.
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and eng.step():
                pass
            eng.run_until_idle()
            assert client.result(r1, 10) == _ref_tokens(m, params, p1, 6)
            assert client.result(r2, 10) == _ref_tokens(m, params, p2, 5)
            with pytest.raises(ServeRejected):
                client.result(r3, 10)
            # Streaming (engine thread drives) + invalid submission.
            eng.start()
            toks = list(client.stream(p1, 6, timeout=30))
            assert toks == _ref_tokens(m, params, p1, 6)
            with pytest.raises(ValueError, match="max_model_len"):
                client.generate([1] * 60, 10, timeout=30)
        finally:
            eng.stop()
            client.close()

    def test_malformed_queue_request_gets_invalid_reply(self, model):
        """Bad field TYPES (int(None), ...) after the reply address is
        known must come back as serve_done(status="invalid"), not a
        silent drop that strands the client at its timeout."""
        from ray_lightning_tpu.cluster.queue import DriverQueue

        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=1,
                                                 block_size=8))
        replies = DriverQueue()
        try:
            eng.queue_handle().put({
                "type": "serve_request", "rid": "bad", "prompt": [1, 2],
                "max_new_tokens": None,
                "reply": [replies.handle.host, replies.handle.port],
            })
            deadline = time.monotonic() + 10
            item = None
            while item is None and time.monotonic() < deadline:
                eng.step()
                try:
                    item = replies.get(timeout=0.2)
                except Exception:
                    item = None
            assert item is not None, "no reply for the malformed request"
            assert item["type"] == "serve_done"
            assert item["status"] == "invalid"
        finally:
            replies.shutdown()
            eng.stop()

    def test_wire_items_are_schema_valid(self, model):
        """Capture real wire traffic and pin it to the schema."""
        from ray_lightning_tpu.telemetry.schema import (
            validate_serve_reply, validate_serve_request,
        )

        m, params = model
        eng = ServeEngine(m, params, ServeConfig(num_slots=1,
                                                 block_size=8))
        sent = []
        orig = eng._reply

        def spy(addr, item):
            sent.append(item)
            orig(addr, item)

        eng._reply = spy
        client = ServeClient(eng.queue_handle())
        try:
            rid = client.submit([1, 2, 3], 3)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and eng.step():
                pass
            eng.run_until_idle()
            client.result(rid, 10)
            # The request as the engine saw it (re-build from client
            # fields) + every reply it actually sent.
            req_item = {
                "type": "serve_request", "rid": rid, "prompt": [1, 2, 3],
                "max_new_tokens": 3, "temperature": 0.0,
                "eos_token_id": None, "deadline_s": None,
                "reply": list(client._reply_addr),
            }
            assert validate_serve_request(req_item) == []
            assert sent, "engine sent no replies"
            for item in sent:
                assert validate_serve_reply(item) == [], item
        finally:
            eng.stop()
            client.close()


class TestCoalescedReplies:
    """One ``serve_batch`` frame a tick per reply address (what the
    engine does; ``coalesce_replies=False`` is the tests' reference, a
    frame a token); what a caller is served does not change."""

    def _serve(self, model, coalesce):
        from ray_lightning_tpu.cluster.queue import QueueHandle
        m, params = model
        kw = {} if coalesce else {"coalesce_replies": False}
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=3, block_size=8, **kw))
        frames = []
        orig = QueueHandle.put

        def spy(handle, item):
            if isinstance(item, dict) and str(
                    item.get("type", "")).startswith("serve_") and (
                    item["type"] != "serve_request"):
                frames.append(item)
            orig(handle, item)

        client = ServeClient(eng.queue_handle())
        prompts = [_rand_prompt(20 + i, 4 + i, m.config.vocab_size)
                   for i in range(3)]
        news = [6, 4, 5]
        try:
            QueueHandle.put = spy
            rids = [client.submit(p, n) for p, n in zip(prompts, news)]
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and eng.step():
                pass
            eng.run_until_idle()
            served = [client.result(r, 10) for r in rids]
        finally:
            QueueHandle.put = orig
            eng.stop()
            client.close()
        assert eng.stats.counters["reply_frames"] == len(frames)
        want = [_ref_tokens(m, params, p, n)
                for p, n in zip(prompts, news)]
        return served, want, frames

    @pytest.mark.parametrize("coalesce", [False, True])
    def test_served_tokens_are_the_reference(self, model, coalesce):
        served, want, _ = self._serve(model, coalesce)
        assert served == want

    def test_one_frame_a_tick_in_order_and_schema_valid(self, model):
        from ray_lightning_tpu.telemetry.schema import validate_serve_reply

        _, _, plain = self._serve(model, False)
        _, _, frames = self._serve(model, True)
        assert all(f["type"] != "serve_batch" for f in plain)
        batches = [f for f in frames if f["type"] == "serve_batch"]
        assert batches, "no tick coalesced its replies"
        for f in frames:
            assert validate_serve_reply(f) == [], f
        # Fewer frames, the same items: 15 tokens and 3 completions.
        items = [i for f in frames
                 for i in (f["items"] if f["type"] == "serve_batch" else [f])]
        assert len(frames) < len(plain) == len(items) == 18
        for rid in {i["rid"] for i in items}:
            mine = [i for i in items if i["rid"] == rid]
            assert [i["index"] for i in mine[:-1]] == list(
                range(len(mine) - 1))
            assert mine[-1]["type"] == "serve_done"

    def test_schema_refuses_empty_and_nested_batches(self):
        from ray_lightning_tpu.telemetry.schema import validate_serve_reply

        tok = {"type": "serve_token", "rid": "r", "index": 0, "token": 1}
        assert validate_serve_reply(
            {"type": "serve_batch", "items": [tok, tok]}) == []
        assert validate_serve_reply({"type": "serve_batch", "items": []})
        assert validate_serve_reply({"type": "serve_batch", "items": [
            {"type": "serve_batch", "items": [tok]}]})
        assert validate_serve_reply({"type": "serve_batch", "items": [
            {**tok, "index": -1}]})


SERIAL = {"coalesce_replies": False, "decode_lookahead": False}


class TestDecodeLookahead:
    """The decode loop runs one tick ahead of the host: the next decode
    is dispatched on this tick's tokens where they lie on the device,
    before they are fetched, and an admission joins that pipeline: its
    first token feeds the next decode on the device and is fetched after
    that decode's dispatch.  What is served does not change, whatever
    happens to a slot while a decode or a prefill is in flight;
    ``SERIAL`` is the reference path."""

    NEWS = [9, 2, 12, 1, 7, 5, 10]

    def _engine(self, model, **kw):
        m, params = model
        return ServeEngine(m, params, ServeConfig(
            num_slots=3, block_size=8, **kw))

    def _served(self, model, temperature, **kw):
        """More requests than slots, lengths that end on different
        ticks: admissions and completions interleave with ticks
        dispatched ahead."""
        m, _ = model
        eng = self._engine(model, **kw)
        prompts = [_rand_prompt(40 + i, 3 + (5 * i) % 11,
                                m.config.vocab_size) for i in range(7)]
        news = self.NEWS
        handles = [eng.submit(p, n, temperature=temperature)
                   for p, n in zip(prompts, news)]
        eng.run_until_idle()
        assert eng._ahead is None      # nothing left in flight at idle
        assert eng._firsts == []
        assert eng.stats.counters["tokens_out"] == sum(news)
        return prompts, [h.result(1) for h in handles], eng.stats.counters

    @pytest.mark.parametrize("temperature", [0.0, 0.8])
    @pytest.mark.parametrize("kw", [{}, SERIAL], ids=["ahead", "serial"])
    def test_served_tokens_are_the_reference(self, model, kw, temperature):
        """Sampling is keyed by position, so the tokens are the same
        under any order of dispatch, at any temperature: greedy against
        the static path, sampled against the serial loop's tokens
        (engine seed and submission order fixed)."""
        m, params = model
        prompts, served, c = self._served(model, temperature, **kw)
        greedy = [_ref_tokens(m, params, p, len(s))
                  for p, s in zip(prompts, served)]
        if temperature == 0.0:
            want = greedy
        else:
            want = self._served(model, temperature, **SERIAL)[1]
            assert want != greedy
        assert served == want
        assert c["prefills"] == len(self.NEWS)
        if kw:
            assert c["decode_ahead"] == 0 == c["decode_fed_on_device"]
            assert c["admit_fed_on_device"] == 0
        else:
            assert (0 < c["decode_fed_on_device"] <= c["decode_ahead"]
                    < c["decode_steps"])
            # Every first token reached the decode after it on the
            # device, but the one that was its request's last.
            assert c["admit_fed_on_device"] == (
                c["prefills"] - self.NEWS.count(1))

    def test_a_slot_ending_by_count_does_not_hold_the_others_back(
            self, model):
        """The host knows a slot's last token by count before it has
        it: the next decode is dispatched for the slots that go on, the
        one that ends computed as the empty slot it is about to be."""
        m, params = model
        eng = self._engine(model)
        p1 = _rand_prompt(65, 5, m.config.vocab_size)
        p2 = _rand_prompt(66, 7, m.config.vocab_size)
        h1, h2 = eng.submit(p1, 4), eng.submit(p2, 9)
        eng.run_until_idle()
        assert h1.result(1) == _ref_tokens(m, params, p1, 4)
        assert h2.result(1) == _ref_tokens(m, params, p2, 9)
        c = eng.stats.counters
        # Every tick but the first was fed on the device, the one after
        # h1's last token too.
        assert c["decode_steps"] == 8
        assert c["decode_fed_on_device"] == 7 == c["decode_ahead"]

    def test_a_request_with_eos_is_never_fed_past_it(self, model):
        """The host cannot test an eos before it has the token: while a
        request carries one, the next decode waits for the fetch (and
        is still dispatched before the replies)."""
        m, params = model
        prompt = _rand_prompt(61, 6, m.config.vocab_size)
        want = _ref_tokens(m, params, prompt, 10)
        eos = want[4]
        cut = want[:want.index(eos) + 1]
        eng = self._engine(model)
        dispatched = []
        decode = eng._decode_fn
        eng._decode_fn = lambda *a: dispatched.append(1) or decode(*a)
        assert eng.generate(prompt, 10, eos_token_id=eos) == cut
        c = eng.stats.counters
        # A decode a token after the first, none after the eos.
        assert len(dispatched) == len(cut) - 1 == c["decode_steps"]
        assert c["decode_fed_on_device"] == 0 == c["admit_fed_on_device"]
        assert c["decode_ahead"] == (c["decode_steps"] - 1 if len(cut) > 2
                                     else 0)
        assert eng._ahead is None

    def test_over_the_client_plane_with_eos(self, model):
        m, params = model
        prompt = _rand_prompt(61, 6, m.config.vocab_size)
        want = _ref_tokens(m, params, prompt, 10)
        eos = want[4]
        cut = want[:want.index(eos) + 1]
        eng = self._engine(model).start()
        client = ServeClient(eng.queue_handle())
        try:
            other = client.submit(_rand_prompt(62, 5, m.config.vocab_size),
                                  12)
            got = list(client.stream(prompt, 10, eos_token_id=eos,
                                     timeout=60))
            assert got == cut
            assert len(client.result(other, 60)) == 12
        finally:
            eng.stop()
            client.close()

    def test_slot_cancelled_while_a_decode_is_in_flight(self, model):
        m, params = model
        eng = self._engine(model)
        p1 = _rand_prompt(71, 5, m.config.vocab_size)
        p2 = _rand_prompt(72, 7, m.config.vocab_size)
        p3 = _rand_prompt(73, 4, m.config.vocab_size)
        h1, h2 = eng.submit(p1, 12), eng.submit(p2, 12)
        while eng._ahead is None:
            assert eng.step()
        assert eng.cancel(h2.rid)
        h3 = eng.submit(p3, 6)          # takes a slot mid-flight
        eng.run_until_idle()
        assert h1.result(1) == _ref_tokens(m, params, p1, 12)
        assert h3.result(1) == _ref_tokens(m, params, p3, 6)
        assert h2.done() and len(h2.tokens) < 12
        assert eng.stats.counters["cancelled"] == 1

    def test_every_slot_cancelled_while_a_decode_is_in_flight(self, model):
        m, params = model
        eng = self._engine(model)
        h1 = eng.submit(_rand_prompt(74, 5, m.config.vocab_size), 12)
        while eng._ahead is None:
            assert eng.step()
        assert eng.cancel(h1.rid)
        eng.step()
        assert eng._ahead is None       # dropped with its last slot
        p2 = _rand_prompt(75, 6, m.config.vocab_size)
        assert eng.generate(p2, 5) == _ref_tokens(m, params, p2, 5)

    def test_a_tick_behind_another_books_no_queueing_as_its_cost(self,
                                                                 model):
        """``decode_us`` feeds the capacity oracle's tick-cost fit: a
        decode dispatched while the one before it still ran counts from
        when that one's tokens were out, not from its own dispatch."""
        m, _ = model
        eng = self._engine(model)
        eng.submit(_rand_prompt(76, 5, m.config.vocab_size), 12)
        while eng._ahead is None:
            assert eng.step()
        # As if dispatched 10 s before the tick it queued behind ended.
        eng._ahead = (eng._ahead[0] - 10.0, *eng._ahead[1:])
        before = eng.stats.counters["decode_us"]
        assert eng.step()
        assert 0 < eng.stats.counters["decode_us"] - before < 5_000_000
        eng.run_until_idle()

    def test_slot_preempted_while_a_decode_is_in_flight(self, model):
        """A pool too small for two whole sequences: the younger is
        preempted while ticks are dispatched ahead, and both still match
        the static path."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=2, block_size=4, num_blocks=8, max_model_len=24))
        p1, p2 = [3, 1, 4, 1], [2, 7, 1]
        h1, h2 = eng.submit(p1, 16), eng.submit(p2, 16)
        eng.run_until_idle()
        assert h1.result(5) == _ref_tokens(m, params, p1, 16)
        assert h2.result(5) == _ref_tokens(m, params, p2, 16)
        c = eng.stats.counters
        assert c["preempted"] >= 1 and c["decode_ahead"] > 0
        assert eng._ahead is None

    @pytest.mark.parametrize("kw", [
        {"prefix_cache": True}, {"prefill_chunk": 8},
        {"max_adapters": 2, "adapter_rank": 4}, {"spec_k": 2}],
        ids=["prefix_cache", "prefill_chunk", "adapters", "draft"])
    def test_serial_loop_where_state_is_booked_between_ticks(self, model,
                                                             kw):
        """Such an engine builds with the default fields, runs the
        serial loop and serves the reference tokens."""
        from ray_lightning_tpu.serve.draft import early_exit_draft

        m, params = model
        draft = {}
        if "spec_k" in kw:
            dm, dp = early_exit_draft(m, params, 1)
            draft = {"draft_module": dm, "draft_params": dp}
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=3, block_size=8, **kw), **draft)
        assert eng.config.decode_lookahead and not eng._pipelined
        prompts = [_rand_prompt(80 + i, 4 + 3 * i, m.config.vocab_size)
                   for i in range(4)]
        news = [9, 3, 7, 5]
        handles = [eng.submit(p, n) for p, n in zip(prompts, news)]
        eng.run_until_idle()
        for h, p, n in zip(handles, prompts, news):
            assert h.result(1) == _ref_tokens(m, params, p, n)
        c = eng.stats.counters
        assert c["decode_ahead"] == 0 == c["decode_fed_on_device"]
        assert c["admit_fed_on_device"] == 0
        assert eng._ahead is None

    # -- an admission in the pipeline -----------------------------------

    def _logged(self, eng, monkeypatch):
        """The engine's prefills, decodes (with their ``seq_lens``
        operand) and phases, in the order the loop makes them."""
        from ray_lightning_tpu.telemetry import spans

        events, lens = [], []
        prefill, decode, then = (eng._prefill_fn, eng._decode_fn,
                                 spans._PhaseCtx.then)
        eng._prefill_fn = lambda *a: events.append("prefill") or prefill(*a)

        def logged_decode(*a):
            events.append("decode")
            lens.append(np.asarray(a[3]).tolist())
            return decode(*a)

        def logged_then(ph, name, **args):
            events.append(name)
            return then(ph, name, **args)

        eng._decode_fn = logged_decode
        monkeypatch.setattr(spans._PhaseCtx, "then", logged_then)
        return events, lens

    @pytest.mark.parametrize("case", ["ahead", "after_idle", "eos",
                                      "serial"])
    def test_order_of_dispatch_on_an_admitting_iteration(
            self, model, monkeypatch, case):
        """Prefill, then the decode for residents plus the admitted
        slot, and only then the host blocks on the first token; an
        ``eos_token_id`` or the serial loop keep the sync at the
        admission, before the decode."""
        m, params = model
        eng = self._engine(model, **(SERIAL if case == "serial" else {}))
        p1 = _rand_prompt(91, 5, m.config.vocab_size)
        p2 = _rand_prompt(92, 7, m.config.vocab_size)
        want2 = _ref_tokens(m, params, p2, 6)
        never = next(t for t in range(m.config.vocab_size)
                     if t not in want2)
        kw = {"eos_token_id": never} if case == "eos" else {}
        h1 = None
        if case != "after_idle":
            h1 = eng.submit(p1, 12)
            for _ in range(3):
                assert eng.step()
            assert (eng._ahead is not None) == (case != "serial")
        events, lens = self._logged(eng, monkeypatch)
        fed_before = eng.stats.counters["admit_fed_on_device"]
        h2 = eng.submit(p2, 6, **kw)
        assert eng.step()
        monkeypatch.undo()
        ev = [e for e in events if e in (
            "prefill", "decode", "admit_wait", "decode_wait")]
        slot2 = 0 if case == "after_idle" else 1
        if case == "ahead":
            # The decode in hand was dispatched an iteration earlier.
            assert ev == ["prefill", "decode", "decode_wait", "admit_wait"]
            assert lens[0][slot2] == len(p2) and lens[0][0] > 0
        elif case == "after_idle":
            # No decode in hand: the prefill is first on the device, so
            # its fetch is first, behind the dispatch of its decode.
            assert ev == ["prefill", "decode", "admit_wait", "decode",
                          "decode_wait"]
            assert lens[0][slot2] == len(p2)
        elif case == "eos":
            assert ev == ["prefill", "admit_wait", "decode_wait", "decode"]
        else:
            assert ev == ["prefill", "admit_wait", "decode", "decode_wait"]
        c = eng.stats.counters
        eng.run_until_idle()
        assert h2.result(1) == want2
        if h1 is not None:
            assert h1.result(1) == _ref_tokens(m, params, p1, 12)
        fed = case in ("ahead", "after_idle")
        assert c["admit_fed_on_device"] - fed_before == (1 if fed else 0)
        assert c["prefills"] == (1 if h1 is None else 2)

    @pytest.mark.parametrize("resident", [False, True],
                             ids=["alone", "beside_a_resident"])
    def test_a_request_of_one_token_is_never_decoded_for(
            self, model, monkeypatch, resident):
        m, params = model
        eng = self._engine(model)
        p1 = _rand_prompt(93, 5, m.config.vocab_size)
        p2 = _rand_prompt(94, 6, m.config.vocab_size)
        h1 = None
        if resident:
            h1 = eng.submit(p1, 8)
            while eng._ahead is None:
                assert eng.step()
        events, lens = self._logged(eng, monkeypatch)
        fed_before = eng.stats.counters["admit_fed_on_device"]
        h2 = eng.submit(p2, 1)
        assert eng.step()
        assert h2.done() and h2.result(1) == _ref_tokens(m, params, p2, 1)
        eng.run_until_idle()
        monkeypatch.undo()
        if resident:
            assert h1.result(1) == _ref_tokens(m, params, p1, 8)
            # Computed as the empty slot it was about to be, every time.
            assert lens and all(row[1] == 0 for row in lens)
        else:
            assert "decode" not in events and "admit_wait" in events
        assert eng.stats.counters["admit_fed_on_device"] == fed_before
        assert eng.scheduler.active_slots == 0

    @pytest.mark.parametrize("how", ["cancelled", "preempted",
                                     "deadline_passed"])
    def test_slot_leaves_between_its_prefill_and_its_first_token(
            self, model, how):
        """What happens to an admitted request while its first token is
        still on the device: a cancel or a preemption is a slot that
        left (skipped at the fetch, nothing emitted), and a deadline
        that passes there was met at admission."""
        m, params = model
        eng = self._engine(model)
        p1 = _rand_prompt(95, 5, m.config.vocab_size)
        p2 = _rand_prompt(96, 7, m.config.vocab_size)
        h1 = eng.submit(p1, 10)
        while eng._ahead is None:
            assert eng.step()
        prefill, seen = eng._prefill_fn, []

        def between(*a):
            out = prefill(*a)
            if not seen:
                seen.append(1)
                if how == "cancelled":
                    assert eng.cancel(h2.rid)
                elif how == "preempted":
                    assert eng.scheduler.preempt_youngest() is h2.request
                else:
                    time.sleep(0.06)
            return out

        eng._prefill_fn = between
        emitted = []
        h2 = eng.submit(p2, 6, on_token=lambda i, t: emitted.append(i),
                        **({"deadline_s": 0.05}
                           if how == "deadline_passed" else {}))
        assert eng.step() and seen
        if how == "deadline_passed":
            assert emitted == [0]
        else:
            assert emitted == [] and eng._firsts == []
        eng.run_until_idle()
        c = eng.stats.counters
        assert h1.result(1) == _ref_tokens(m, params, p1, 10)
        if how == "cancelled":
            assert h2.done() and h2.tokens == [] and c["cancelled"] == 1
        else:
            assert h2.result(1) == _ref_tokens(m, params, p2, 6)
            assert emitted == list(range(6)) and c["expired"] == 0
            assert h2.request.preemptions == (how == "preempted")
        assert eng._ahead is None and eng.scheduler.active_slots == 0

    def test_two_admissions_in_one_iteration(self, model):
        """Their prefills queue one behind the other, one decode takes
        both first tokens on the device, and the scatter that writes
        them compiled once, with the first admission ever."""
        m, params = model
        eng = self._engine(model)
        prompts = [_rand_prompt(97 + i, 4 + i, m.config.vocab_size)
                   for i in range(3)]      # one bucket
        h1 = eng.submit(prompts[0], 14)
        while eng._ahead is None:
            assert eng.step()
        feeds, feed = [], eng._feed_fn
        eng._feed_fn = lambda *a: feeds.append(int(a[1])) or feed(*a)
        compiles = compile_event_count()
        h2, h3 = eng.submit(prompts[1], 5), eng.submit(prompts[2], 7)
        assert eng.step()
        assert sorted(feeds) == [1, 2]
        assert {s for s, _ in eng._ahead[1]} == {0, 1, 2}
        assert len(h2.tokens) == 1 == len(h3.tokens)
        eng.run_until_idle()
        assert compile_event_count() == compiles
        for h, p, n in zip((h1, h2, h3), prompts, (14, 5, 7)):
            assert h.result(1) == _ref_tokens(m, params, p, n)
        c = eng.stats.counters
        assert c["admit_fed_on_device"] == 3 == c["prefills"]

    @pytest.mark.parametrize("admitting", [False, True])
    def test_what_waits_holds_the_decode_back_unless_a_prefill_is_queued(
            self, model, admitting):
        """A request waits for the slot that falls free with this tick:
        the decode is held back so that the next iteration admits it
        into the very next one.  Not on an iteration that admits: its
        prefill is on the device, the decode goes behind it now, and
        what waits joins a tick later."""
        m, params = model
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=3 if admitting else 2, block_size=8))
        prompts = [_rand_prompt(101 + i, 4 + i, m.config.vocab_size)
                   for i in range(4)]
        news = [12, 3, 6, 5]
        handles = [eng.submit(p, n) for p, n in zip(prompts[:2], news)]
        assert eng.step() and eng._ahead is not None
        # The second request ends, by count, with the tick in hand.
        if admitting:
            handles.append(eng.submit(prompts[2], news[2]))
        handles.append(eng.submit(prompts[3], news[3]))     # waits
        fed = eng.stats.counters["admit_fed_on_device"]
        assert eng.step() and eng.scheduler.queue_depth == 1
        if admitting:
            assert {s for s, _ in eng._ahead[1]} == {0, 2}
        else:
            assert eng._ahead is None
        eng.run_until_idle()
        assert eng.stats.counters["admit_fed_on_device"] - fed == (
            2 if admitting else 1)
        for h, p in zip(handles, [0, 1, 2, 3] if admitting else [0, 1, 3]):
            assert h.result(1) == _ref_tokens(m, params, prompts[p],
                                              news[p])
