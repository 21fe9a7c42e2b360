"""Multi-tenant LoRA multiplexing on the serving plane.

The correctness bar: a request decoded through adapter k must produce
EXACTLY the tokens ``generate()`` produces on the merged model — for
every tenant in a ≥4-adapter pool, in mixed-tenant batches, composing
with speculative decoding and the disaggregated prefill→handoff path —
while the compiled program set never grows with the tenant count
(zero steady-state recompiles across joins and hot-adds).  On top:
the pool's slot registry discipline (free-list reuse, typed misuse
errors, in-use removal refused), the adapter wire codec, and the
scheduler's per-tenant admission caps + deficit-round-robin fairness.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_tpu.models.gpt import (
    extract_lora, synthetic_lora_adapter,
)
from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.lora import (
    AdapterPool, decode_adapter, encode_adapter, validate_adapter,
)
from ray_lightning_tpu.telemetry import compile_event_count

from utils import rand_prompt as _rand_prompt
from utils import reference_tokens as _ref_tokens
from utils import tiny_gpt

pytestmark = pytest.mark.serve

RANK = 4


def _make_tenant(params, lora_cfg, seed):
    """One synthetic tenant via the shared builder (random non-zero
    factors → distinct greedy stream): ``(adapter, merged_params)``."""
    return synthetic_lora_adapter(params, lora_cfg,
                                  jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def model():
    """Base model + 5 tenants (4 preloaded in tests, 1 for hot-add)."""
    import dataclasses

    m, params = tiny_gpt()
    lora_cfg = dataclasses.replace(m.config, lora_rank=RANK)
    tenants = {f"t{i}": _make_tenant(params, lora_cfg, seed=10 + i)
               for i in range(5)}
    adapters = {k: v[0] for k, v in tenants.items()}
    merged = {k: v[1] for k, v in tenants.items()}
    return m, params, adapters, merged


def _pool_engine(m, params, adapters, max_adapters=6, **cfg_kw):
    kw = dict(num_slots=6, block_size=8)
    kw.update(cfg_kw)
    return ServeEngine(
        m, params,
        ServeConfig(max_adapters=max_adapters, adapter_rank=RANK, **kw),
        adapters=adapters,
    )


# ---------------------------------------------------------------------------
# AdapterPool: slot registry discipline (host-side, one tiny pool)
# ---------------------------------------------------------------------------

class TestAdapterPool:
    @pytest.fixture()
    def pool(self, model):
        m, _, _, _ = model
        return AdapterPool(m.config, max_adapters=2, rank=RANK)

    def test_capacity_and_lifo_reuse(self, pool, model):
        _, _, adapters, _ = model
        s0 = pool.add("a", adapters["t0"])
        s1 = pool.add("b", adapters["t1"])
        assert 0 not in (s0, s1)  # slot 0 = the NULL/base adapter
        with pytest.raises(RuntimeError, match="pool full"):
            pool.add("c", adapters["t2"])
        pool.remove("b")
        assert pool.add("c", adapters["t2"]) == s1  # LIFO reuse
        assert pool.names() == ["a", "c"]
        assert pool.loaded == 2 and pool.slots_free == 0
        assert pool.loads == 3 and pool.unloads == 1

    def test_replace_reuses_slot(self, pool, model):
        _, _, adapters, _ = model
        slot = pool.add("a", adapters["t0"])
        assert pool.add("a", adapters["t1"]) == slot
        assert pool.loaded == 1

    def test_typed_misuse(self, pool, model):
        m, _, adapters, _ = model
        with pytest.raises(KeyError):
            pool.remove("ghost")
        with pytest.raises(KeyError):
            pool.slot_of("ghost")
        with pytest.raises(ValueError, match="missing factor"):
            pool.add("a", {"qkv_a": np.zeros((1,))})
        bad = dict(adapters["t0"])
        bad["qkv_b"] = np.zeros((m.config.n_layer, RANK + 1,
                                 3 * m.config.d_model), np.float32)
        with pytest.raises(ValueError, match="rank"):
            pool.add("a", bad)
        with pytest.raises(ValueError, match="dict"):
            validate_adapter([1, 2], m.config, RANK)

    def test_snapshot_shape(self, pool, model):
        _, _, adapters, _ = model
        pool.add("a", adapters["t0"])
        snap = pool.snapshot()
        assert snap["loaded"] == 1 and snap["slots_free"] == 1
        assert snap["max_adapters"] == 2 and snap["rank"] == RANK
        assert snap["impl"] in ("xla", "pallas")


class TestAdapterCodec:
    def test_encode_decode_roundtrip(self, model):
        _, _, adapters, _ = model
        adapter = dict(adapters["t0"])
        blob = encode_adapter(adapter)
        back = decode_adapter({"type": "serve_adapter_load",
                               "name": "t0", "rank": RANK,
                               "data": blob})
        assert back["scale"] == pytest.approx(float(adapter["scale"]))
        for key in ("qkv_a", "qkv_b", "proj_a", "proj_b"):
            np.testing.assert_array_equal(
                np.asarray(back[key]), np.asarray(adapter[key])
            )

    def test_extract_requires_adapters(self, model):
        import dataclasses

        m, params, _, _ = model
        lora_cfg = dataclasses.replace(m.config, lora_rank=RANK)
        with pytest.raises(ValueError, match="no LoRA adapters"):
            extract_lora(params, lora_cfg)
        with pytest.raises(ValueError, match="lora_rank"):
            extract_lora(params, m.config)


# ---------------------------------------------------------------------------
# BGMV: both arms against a dense per-row reference
# ---------------------------------------------------------------------------

class TestBgmv:
    """``ops/lora.py``: the gathered-einsum arm everywhere, and the
    Pallas kernel under the interpreter off-TPU (same machinery every
    optional kernel uses), both against a dense per-row reference."""

    def _case(self, seed=0, W=5, d=16, r=4, k=12, N=3):
        rng = np.random.default_rng(seed)
        h = rng.standard_normal((W, d)).astype(np.float32)
        a = rng.standard_normal((N, d, r)).astype(np.float32)
        b = rng.standard_normal((N, r, k)).astype(np.float32)
        a[0] = 0.0
        b[0] = 0.0  # slot 0 = the NULL adapter
        ids = rng.integers(0, N, size=(W,)).astype(np.int32)
        ref = np.stack([h[w] @ a[ids[w]] @ b[ids[w]]
                        for w in range(W)])
        return h, a, b, ids, ref

    def test_xla_and_pallas_match_dense_reference(self):
        from ray_lightning_tpu.ops.lora import bgmv_pallas, bgmv_xla

        h, a, b, ids, ref = self._case()
        got_xla = np.asarray(bgmv_xla(*map(jnp.asarray, (h, a, b, ids))))
        np.testing.assert_allclose(got_xla, ref, rtol=1e-5, atol=1e-5)
        got_pl = np.asarray(
            bgmv_pallas(*map(jnp.asarray, (h, a, b, ids)))
        )
        np.testing.assert_allclose(got_pl, ref, rtol=1e-5, atol=1e-5)

    def test_null_slot_delta_is_exactly_zero(self):
        from ray_lightning_tpu.ops.lora import lora_delta

        h, a, b, _, _ = self._case()
        zero_ids = jnp.zeros((h.shape[0],), jnp.int32)
        for impl in ("xla", "pallas"):
            got = np.asarray(lora_delta(
                jnp.asarray(h), jnp.asarray(a), jnp.asarray(b),
                zero_ids, impl=impl,
            ))
            assert (got == 0.0).all(), impl

    def test_three_dim_form_repeats_ids_per_position(self):
        from ray_lightning_tpu.ops.lora import lora_delta

        h, a, b, ids, ref = self._case(W=6)
        B, T = 2, 3
        got = np.asarray(lora_delta(
            jnp.asarray(h.reshape(B, T, -1)), jnp.asarray(a),
            jnp.asarray(b), jnp.asarray(ids.reshape(B, T)[:, 0]),
        ))
        # Per-SEQUENCE ids: rows of one sequence share its adapter.
        seq_ids = np.repeat(ids.reshape(B, T)[:, 0], T)
        ref_seq = np.stack([h[w] @ a[seq_ids[w]] @ b[seq_ids[w]]
                            for w in range(B * T)])
        np.testing.assert_allclose(
            got.reshape(B * T, -1), ref_seq, rtol=1e-5, atol=1e-5
        )

    def test_resolve_respects_forced_arm(self, monkeypatch):
        from ray_lightning_tpu.ops import lora as ops_lora

        monkeypatch.setenv("RLT_LORA_BGMV", "pallas")
        assert ops_lora.resolve_bgmv_impl() \
            == "pallas"
        monkeypatch.setenv("RLT_LORA_BGMV", "xla")
        assert ops_lora.resolve_bgmv_impl() \
            == "xla"
        monkeypatch.delenv("RLT_LORA_BGMV")
        # Off-TPU the gather is the selected path.
        assert ops_lora.resolve_bgmv_impl() \
            == "xla"


# ---------------------------------------------------------------------------
# Engine: per-tenant greedy parity + the zero-recompile contract
# ---------------------------------------------------------------------------

class TestEnginePool:
    def test_four_tenant_mixed_batch_parity(self, model):
        """Acceptance bar: adapter k's engine output is token-for-token
        generate() on the merged model, for every tenant of a 4-adapter
        pool — submitted as ONE mixed batch alongside a base request."""
        m, params, adapters, merged = model
        pre = {k: adapters[k] for k in ("t0", "t1", "t2", "t3")}
        eng = _pool_engine(m, params, pre)
        prompt = _rand_prompt(1, 8)
        try:
            handles = {k: eng.submit(prompt, 8, adapter=k) for k in pre}
            handles["base"] = eng.submit(prompt, 8)
            eng.run_until_idle()
            outs = {k: h.result(0) for k, h in handles.items()}
        finally:
            eng.stop()
        assert outs["base"] == _ref_tokens(m, params, prompt, 8)
        streams = set()
        for k in pre:
            ref = _ref_tokens(m, merged[k], prompt, 8)
            assert outs[k] == ref, k
            streams.add(tuple(ref))
        # The tenants must actually be distinct models, or the parity
        # above proves nothing about per-slot application.
        assert len(streams) > 1

    def test_zero_recompiles_across_joins_and_hot_add(self, model):
        m, params, adapters, merged = model
        pre = {k: adapters[k] for k in ("t0", "t1", "t2", "t3")}
        eng = _pool_engine(m, params, pre)
        prompt = _rand_prompt(2, 8)
        try:
            # Warm every program (submit + drive, not generate(): its
            # wall-clock result timeout can expire under whole-suite
            # load while XLA compiles the program set).
            eng.submit(prompt, 4)
            eng.run_until_idle()
            before = compile_event_count()
            handles = [eng.submit(_rand_prompt(3 + i, 8), 6, adapter=k)
                       for i, k in enumerate(pre)]
            eng.add_adapter("t4", adapters["t4"])   # hot join
            handles.append(eng.submit(prompt, 6, adapter="t4"))
            eng.run_until_idle()
            assert all(h.done() for h in handles)
            assert compile_event_count() - before == 0
            assert handles[-1].result(0) == _ref_tokens(
                m, merged["t4"], prompt, 6
            )
        finally:
            eng.stop()

    def test_unknown_and_pool_less_rejections(self, model):
        m, params, adapters, _ = model
        eng = _pool_engine(m, params, {"t0": adapters["t0"]})
        try:
            with pytest.raises(ValueError, match="unknown adapter"):
                eng.submit([1, 2, 3], 4, adapter="ghost")
        finally:
            eng.stop()
        plain = ServeEngine(m, params,
                            ServeConfig(num_slots=2, block_size=8))
        try:
            with pytest.raises(ValueError, match="no adapter pool"):
                plain.submit([1, 2, 3], 4, adapter="t0")
        finally:
            plain.stop()

    def test_config_misuse_is_typed(self, model):
        m, params, adapters, _ = model
        with pytest.raises(ValueError, match="max_adapters"):
            ServeEngine(m, params,
                        ServeConfig(num_slots=2, block_size=8),
                        adapters={"t0": adapters["t0"]})
        with pytest.raises(ValueError, match="adapter_rank"):
            ServeEngine(m, params,
                        ServeConfig(num_slots=2, block_size=8,
                                    max_adapters=2))

    def test_in_use_removal_refused_then_slot_reuse_serves_clean(
            self, model):
        """Removing (or replacing) an adapter a live request decodes
        through is refused; after completion the freed slot re-issued
        to a NEW tenant serves the new tenant's delta, not the old."""
        m, params, adapters, merged = model
        eng = _pool_engine(m, params, {"t0": adapters["t0"]},
                           max_adapters=1)
        prompt = _rand_prompt(4, 8)
        try:
            h = eng.submit(prompt, 8, adapter="t0")
            with pytest.raises(RuntimeError, match="drain"):
                eng.remove_adapter("t0")
            with pytest.raises(RuntimeError, match="mid-stream"):
                eng.add_adapter("t0", adapters["t1"])
            eng.run_until_idle()
            assert h.result(0) == _ref_tokens(m, merged["t0"], prompt, 8)
            eng.remove_adapter("t0")
            eng.add_adapter("t1", adapters["t1"])   # reuses the slot
            h2 = eng.submit(prompt, 8, adapter="t1")
            eng.run_until_idle()
            assert h2.result(0) == _ref_tokens(
                m, merged["t1"], prompt, 8
            )
        finally:
            eng.stop()

    def test_per_tenant_accounting_in_snapshot(self, model):
        m, params, adapters, _ = model
        from ray_lightning_tpu.telemetry.schema import (
            validate_serve_snapshot,
        )

        eng = _pool_engine(m, params, {"t0": adapters["t0"],
                                       "t1": adapters["t1"]})
        try:
            for k in ("t0", "t1"):
                eng.submit(_rand_prompt(5, 8), 4, adapter=k)
            eng.run_until_idle()
            snap = eng.snapshot()
        finally:
            eng.stop()
        assert validate_serve_snapshot(snap) == []
        assert snap["adapters"]["t0"]["tokens_out"] == 4
        assert snap["adapters"]["t1"]["completed"] == 1
        assert snap["gauges"]["lora_fairness_spread"] == 1.0
        assert snap["gauges"]["lora_adapters_loaded"] == 2


# ---------------------------------------------------------------------------
# Scheduler: per-tenant caps + deficit-round-robin grants (jax-free)
# ---------------------------------------------------------------------------

def _sched(num_slots=1, max_queue=16, per_adapter=None):
    from ray_lightning_tpu.serve.kv_cache import BlockAllocator
    from ray_lightning_tpu.serve.scheduler import Scheduler

    return Scheduler(num_slots, BlockAllocator(64), block_size=4,
                     max_blocks_per_seq=8, buckets=[4, 8],
                     max_queue=max_queue,
                     max_queue_per_adapter=per_adapter)


def _req(rid, adapter=None, preemptions=0):
    from ray_lightning_tpu.serve.scheduler import Request

    r = Request(rid=rid, prompt=[1, 2, 3], max_new_tokens=1,
                adapter=adapter)
    r.preemptions = preemptions
    return r


class TestSchedulerFairness:
    def _drain_one(self, s):
        """Admit one request on the 1-slot scheduler, complete it, and
        return its rid."""
        admissions, _ = s.poll(now=0.0)
        assert len(admissions) == 1
        slot, req, _ = admissions[0]
        assert s.append_token(slot, 7)  # max_new_tokens=1 -> done
        s.finish(slot)
        return req.rid

    def test_drr_rotates_across_tenants(self, s=None):
        """One tenant's burst cannot monopolize slot turnover: grants
        cycle a -> b -> c -> a... while FIFO holds within a tenant."""
        s = _sched()
        for rid, tenant in (("a1", "a"), ("a2", "a"), ("a3", "a"),
                            ("b1", "b"), ("c1", "c")):
            assert s.submit(_req(rid, adapter=tenant))
        order = [self._drain_one(s) for _ in range(5)]
        assert order == ["a1", "b1", "c1", "a2", "a3"]

    def test_base_traffic_is_a_tenant_key_too(self):
        """None (the base model) cycles like any other key — pre-LoRA
        single-key traffic reduces exactly to FIFO."""
        s = _sched()
        for rid, tenant in (("n1", None), ("n2", None), ("a1", "a")):
            assert s.submit(_req(rid, adapter=tenant))
        assert [self._drain_one(s) for _ in range(3)] \
            == ["n1", "a1", "n2"]
        s2 = _sched()
        for rid in ("x1", "x2", "x3"):
            assert s2.submit(_req(rid))
        assert [self._drain_one(s2) for _ in range(3)] \
            == ["x1", "x2", "x3"]

    def test_preempted_outranks_fairness(self):
        s = _sched()
        assert s.submit(_req("a1", adapter="a"))
        assert s.submit(_req("b1", adapter="b", preemptions=1))
        # DRR alone would grant "a1" first (canonical order); the
        # preempted request's front-requeue contract wins.
        assert self._drain_one(s) == "b1"

    def test_per_adapter_cap_is_per_tenant(self):
        from ray_lightning_tpu.serve.scheduler import RequestState

        s = _sched(max_queue=16, per_adapter=2)
        assert s.submit(_req("a1", adapter="a"))
        assert s.submit(_req("a2", adapter="a"))
        burst = _req("a3", adapter="a")
        assert not s.submit(burst)          # tenant a saturated its cap
        assert burst.state is RequestState.REJECTED
        assert s.submit(_req("b1", adapter="b"))   # b keeps its seats
        assert s.submit(_req("n1"))                # and so does base

    def test_engine_surfaces_per_adapter_rejection(self, model):
        m, params, adapters, _ = model
        eng = _pool_engine(m, params, {"t0": adapters["t0"]},
                           num_slots=1, max_queue_per_adapter=1)
        try:
            # Slot busy + one queued for t0: the next t0 submission
            # must bounce while the pool-wide queue still has room.
            eng.submit(_rand_prompt(6, 8), 8, adapter="t0")
            eng.submit(_rand_prompt(7, 8), 8, adapter="t0")
            h = eng.submit(_rand_prompt(8, 8), 8, adapter="t0")
            assert h.status == "rejected"
            h2 = eng.submit(_rand_prompt(9, 8), 8)   # base unaffected
            eng.run_until_idle()
            assert h2.done()
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# Composition: speculative decoding + the disaggregated handoff path
# ---------------------------------------------------------------------------

class TestSpecCompose:
    def test_spec_engine_matches_merged_generate(self, model):
        """The TARGET carries the tenant's adapter; a base-model draft
        merely proposes, and greedy verification corrects every
        disagreement — so spec output through adapter k is still
        token-for-token the merged model's, at zero steady-state
        recompiles."""
        from ray_lightning_tpu.serve.draft import early_exit_draft

        m, params, adapters, merged = model
        draft, draft_params = early_exit_draft(m, params, 1)
        pre = {k: adapters[k] for k in ("t0", "t1")}
        eng = ServeEngine(
            m, params,
            ServeConfig(num_slots=4, block_size=8, spec_k=2,
                        max_adapters=4, adapter_rank=RANK),
            draft_module=draft, draft_params=draft_params,
            adapters=pre,
        )
        prompt = _rand_prompt(11, 8)
        try:
            # Warm EVERY spec-engine program deterministically: the
            # default-spec request compiles prefill/draft/verify, and
            # the spec=0 request forces the plain-decode FALLBACK tick
            # (+ its draft-cache mirror ops) — whether a spec request
            # alone ever hits the fallback depends on its acceptance
            # pattern, which must not decide what the recompile pin
            # below sees.
            eng.submit(prompt, 4)
            eng.submit(prompt, 4, spec=0)
            eng.run_until_idle()
            before = compile_event_count()
            handles = {k: eng.submit(prompt, 8, adapter=k) for k in pre}
            handles["base"] = eng.submit(prompt, 8)
            eng.run_until_idle()
            assert compile_event_count() - before == 0
            for k in pre:
                assert handles[k].result(0) == _ref_tokens(
                    m, merged[k], prompt, 8
                ), k
            assert handles["base"].result(0) == _ref_tokens(
                m, params, prompt, 8
            )
        finally:
            eng.stop()


class TestHandoffLoadRace:
    def test_handoff_outrunning_adapter_load_defers_not_fails(
            self, model):
        """The prefill worker's handoff rides its OWN connection and
        can reach the replica before the router's serve_adapter_load
        frame: the engine must DEFER the admission (bounded) until the
        load lands — never fail a valid request 'unknown adapter' —
        and the deferred import must still match the merged model."""
        import time as _time

        from ray_lightning_tpu.cluster.queue import DriverQueue
        from ray_lightning_tpu.serve.dist.handoff import (
            make_adapter_load_item, make_dispatch_item, request_fields,
        )
        from ray_lightning_tpu.serve.dist.prefill import PrefillRunner
        from ray_lightning_tpu.serve.lora import encode_adapter

        m, params, adapters, merged = model
        scfg = ServeConfig(num_slots=2, block_size=8, max_adapters=2,
                           adapter_rank=RANK)
        eng = ServeEngine(m, params, scfg)
        replies = DriverQueue()
        beats = DriverQueue()
        worker = PrefillRunner("pw", m, params, scfg, beats.handle,
                               beat_s=60.0)
        worker.adapters.add("t0", adapters["t0"])
        handle = eng.queue_handle()
        prompt = _rand_prompt(13, 8)
        try:
            req = request_fields(
                "r1", prompt, 8,
                reply=(replies.handle.host, replies.handle.port),
                sample_seed=0, adapter="t0",
            )
            worker._inbox.handle.put(
                make_dispatch_item(req, (handle.host, handle.port))
            )
            assert worker.step(timeout=10)
            # The handoff is in flight to the engine; its tenant is NOT
            # loaded.  Drive until the engine has seen (and deferred)
            # it — not replied invalid.
            deadline = _time.monotonic() + 10
            while not eng._deferred_inbox \
                    and _time.monotonic() < deadline:
                eng.step()
                _time.sleep(0.01)
            assert eng._deferred_inbox, "handoff was not deferred"
            assert eng.stats.counters.get("completed", 0) == 0
            # The (late) load frame lands; the next drains admit it.
            handle.put(make_adapter_load_item(
                "t0", RANK, data=encode_adapter(adapters["t0"]),
            ))
            done = None
            deadline = _time.monotonic() + 30
            while done is None and _time.monotonic() < deadline:
                eng.step()
                try:
                    item = replies.get_nowait()
                except Exception:  # noqa: BLE001 - empty queue
                    _time.sleep(0.01)
                    continue
                # The last token and the completion share a tick's frame.
                for sub in item.get("items", [item]):
                    if sub.get("type") == "serve_done":
                        done = sub
            assert done is not None and done["status"] == "finished"
            assert done["tokens"] == _ref_tokens(
                m, merged["t0"], prompt, 8
            )
            assert eng.stats.counters["kv_imports"] == 1
        finally:
            worker.close()
            beats.shutdown()
            eng.stop()
            replies.shutdown()


class TestDisaggCompose:
    def test_fleet_routes_hot_loads_and_matches_merged(self, model):
        """Through the full prefill → KV-handoff → decode path: the
        router hot-loads the tenant onto BOTH the prefill worker and
        the decode replica (lazy serve_adapter_load frames), placement
        prefers holders, and the streamed tokens are the merged
        model's."""
        from ray_lightning_tpu.serve.client import ServeClient
        from ray_lightning_tpu.serve.dist import launch_inproc_fleet

        m, params, adapters, merged = model
        pre = {k: adapters[k] for k in ("t0", "t1")}
        fleet = launch_inproc_fleet(
            m, params,
            ServeConfig(num_slots=4, block_size=8, max_adapters=4,
                        adapter_rank=RANK),
            n_replicas=1, n_prefill=1, lost_after_s=30.0,
            adapters=pre,
        )
        client = ServeClient(fleet.queue_handle())
        prompt = _rand_prompt(12, 8)
        try:
            rids = {k: client.submit(prompt, 8, adapter=k) for k in pre}
            rids["base"] = client.submit(prompt, 8)
            outs = {k: client.result(rid, timeout=240)
                    for k, rid in rids.items()}
            for k in pre:
                assert outs[k] == _ref_tokens(m, merged[k], prompt, 8), k
            assert outs["base"] == _ref_tokens(m, params, prompt, 8)
            # Unknown tenant: the router's typed invalid, never a
            # silent base-model stream.
            with pytest.raises(ValueError, match="unknown adapter"):
                client.result(client.submit(prompt, 4, adapter="ghost"),
                              timeout=60)
            snap = fleet.router.snapshot()
            # One load per member per tenant, at most (lazy + cached).
            assert 2 <= snap["counters"]["adapter_loads_sent"] <= 4
            from ray_lightning_tpu.telemetry.schema import (
                validate_router_snapshot,
            )

            assert validate_router_snapshot(snap) == []
            assert snap["replicas"][0].get("adapters", 0) >= 2
            assert snap["workers"][0].get("adapters", 0) >= 2
        finally:
            client.close()
            fleet.close()
