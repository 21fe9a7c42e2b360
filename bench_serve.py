"""Serving SLO bench: continuous batching under a Poisson load generator.

Prints ONE JSON line with a schema-gated ``serve`` block
(``telemetry/schema.py::validate_bench_serve``, wired into
``tools/check_telemetry_schema.py``) — the serving half of the perf
trajectory alongside ``bench.py``'s training line.

Three phases, all through the REAL :class:`ServeEngine` path:

1. **warmup** — compile every program the steady state needs (one
   prefill per bucket the traffic uses + the one decode program), then
   pin the telemetry recompile counter;
2. **headline (closed loop)** — saturating load: every request
   submitted at once, uniform shape, engine driven to idle.  Reports
   ``requests_per_sec`` / ``tokens_per_sec`` / token-latency
   percentiles, asserts ZERO steady-state recompiles, and runs the A/B:
   the SAME request set through sequential one-at-a-time
   ``generate()`` calls (compiled once, warmed) →
   ``continuous_vs_sequential`` — the acceptance bar is ≥ 1.5x at
   batch-capable load;
3. **rate sweep (open loop)** — Poisson arrivals at fractions of the
   measured capacity; each arm reports offered vs achieved rate, TTFT
   and token-latency percentiles — the latency-vs-load curve an SLO is
   set against.

Methodology notes (docs/SERVING.md): the load generator is
deterministic (seeded exponential inter-arrivals); latency families
are nearest-rank percentiles over the phase's full token stream; the
sequential baseline uses the same prompt shapes so neither arm pays a
compile or padding tax the other doesn't.

A fourth phase benches **speculative decoding** (the ``spec_decode``
block, ``validate_bench_spec_decode``): a shallow draft proposes
``RLT_SPEC_K`` (default 4) tokens per tick and the deeper target
verifies them in one fixed-width dispatch, A/B'd against the same
target on a plain (non-spec) engine.  The draft/target pair is
CONSTRUCTED, not trained: the target is the draft plus identity tail
blocks (``serve/draft.py::pad_identity_layers``) — full-depth compute,
draft-equal logits — so the headline arm measures the program
machinery at a known ~1.0 acceptance rate, and the acceptance sweep
perturbs the tail to scan realistic acceptance regimes without
training anything.

A sixth phase benches **distributed tracing** (the ``trace`` block,
``validate_bench_trace``): an inproc disaggregated fleet (replicas +
prefill worker behind the router) runs with request tracing ON, its
per-component span exports are stitched
(``telemetry/trace_collect.py``), and the block reports stitch
coverage (fraction of completed requests with a complete
``queue_wait → … → first_token`` phase chain — the ≥0.95 bar),
per-phase p50/p95, and the measured closed-loop headline overhead of
cheap-tier tracing (ONE monolith engine toggling its tracer flag,
median of adjacent alternating on/off pairs — the <2% bar).

A seventh phase benches **multi-tenant LoRA multiplexing** (the
``multi_lora`` block, ``validate_bench_multi_lora``):
``RLT_MAX_ADAPTERS`` (default 8) tenants' adapters stacked in ONE
resident engine's pool (``serve/lora.py``) and served as mixed batches
— per-slot ``adapter_ids`` operands, so any tenant mix shares the
compiled-once program set — A/B'd against the **merge-and-swap**
baseline (fold tenant k's factors into the weights, upload, serve its
requests alone, swap for the next tenant: the pre-pool shape where
every tenant needs its own resident merged copy).  Two of the tenants
hot-join THROUGH the pool mid-load; both arms pin their steady-state
recompile counters at ZERO, every tenant's multiplexed stream is
token-for-token its merged baseline's (``greedy_parity``), and
``fairness_spread`` reports min/max lifetime tokens across tenants
under the uniform offered load.

An eighth phase benches **prefix-aware KV reuse** (the
``prefix_cache`` block, ``validate_bench_prefix_cache``): a
shared-system-prompt mix (every request is the same 6-block prefix
plus a unique one-block tail, ``prefix_share`` ≈ 0.86) through a
cache-on engine — resident prefix blocks claimed by refcount, only
the unique tail prefilled through the suffix chunk program — A/B'd
against the same mix on a cache-off engine.  Sequential closed loop
(one request in flight), so the TTFT percentiles are the prefill path
itself; acceptance is ``ttft_speedup`` ≥ 1.5x with bitwise token
parity, a live hit-rate, and steady-state recompiles pinned at ZERO
in both arms.  ``RLT_PREFIX_CACHE=0`` skips the phase.

A ninth phase calibrates the **SLO & capacity plane** (the ``slo``
block, ``validate_bench_slo``): a fresh plane-on engine serves a cold
(0.5x capacity) Poisson arm from which the headroom oracle
(``serve/capacity.py``) must PREDICT the saturation knee — per-slot
service rate is load-invariant, so half load calibrates the ceiling —
then a hot (1.5x) arm measures the real knee (±20% bar) and must trip
the multi-window burn-rate alert (``telemetry/slo.py``) that the cold
arm kept silent.  Steady-state recompiles stay pinned at ZERO with
the plane on, and the plane's closed-loop overhead (ONE engine
toggling the plane, median of adjacent alternating-order pairs) must
sit under the 2% bar.  ``RLT_SLO=0`` skips the phase.

A fifth phase benches **disaggregated serving** (the ``serve_disagg``
block, ``validate_bench_serve_disagg``): a real actor fleet —
``RLT_DISAGG_REPLICAS`` (default 2) decode replicas +
``RLT_DISAGG_PREFILL`` (default 1) prefill workers, each its own
process — behind the load-aware router, driven open-loop at a
fraction of measured monolith capacity, reporting throughput vs the
monolith (process contention makes this an honest <1x on the 2-core
CPU container; whether disaggregation pays on chips is not measured)
and pinning per-replica steady-state recompiles at ZERO from the
replicas' beat counters.  The **chaos arm** then SIGKILLs the busiest
decode replica mid-sweep under Poisson load:
zero lost requests (failover re-submission onto survivors), with
failover detection latency and client-deduped re-emission counts in
the block.  ``RLT_DISAGG_REPLICAS=0`` skips the phase.

The final phase is the **serving-chaos A/B** (the ``serve_chaos``
block, ``validate_bench_serve_chaos``): a planned drain with
``RLT_MIGRATE_ON_DRAIN=1`` live-migrates resident KV blocks +
scheduler position to a survivor (decode resumes mid-sequence, zero
recomputed prefill) while an abrupt kill takes the recompute-failover
path; both arms report time-to-recovery (the migration must beat the
failover), bitwise parity vs an uninterrupted monolith — sampled AND
greedy — zero lost requests, and steady-state recompiles pinned at
ZERO.  The full fault x recovery matrix (beat blackhole, torn
handoff, shm vanish, hedging, brownout) lives in
``tools/chaos_serve_sweep.py``.  ``RLT_SERVE_CHAOS=0`` skips the
phase.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models.generate import generate
from ray_lightning_tpu.models.gpt import GPT, GPTConfig
from ray_lightning_tpu.serve.draft import pad_identity_layers
from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.metrics import ServeStats
from ray_lightning_tpu.telemetry import compile_event_count
from ray_lightning_tpu.telemetry.schema import (
    validate_bench_multi_lora, validate_bench_prefix_cache,
    validate_bench_serve, validate_bench_serve_chaos,
    validate_bench_serve_disagg, validate_bench_slo,
    validate_bench_spec_decode, validate_bench_trace,
)

PROMPT_LEN = 16
MAX_NEW = 16
HEADLINE_REQUESTS = 48
SWEEP_REQUESTS = 24
SWEEP_FRACTIONS = (0.5, 0.9, 1.5)   # of measured closed-loop capacity
SPEC_REQUESTS = 16
# Longer generations than the headline arm: speculation pays per decode
# tick, so the arm amortizes its (two-model) prefill cost the way a
# real serving mix does.
SPEC_MAX_NEW = 32
SPEC_NOISE_SWEEP = (0.002, 0.01)    # identity-tail perturbation scales


def _prompts(n: int, vocab: int, length: int = PROMPT_LEN,
             seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=(length,)).tolist()
            for _ in range(n)]


def _lat(snapshot: dict, family: str, q: str):
    return (snapshot["latency"].get(family) or {}).get(q)


def _closed_loop(engine: ServeEngine, prompts: list) -> dict:
    """Saturating load: submit everything, drive to idle."""
    engine.stats = ServeStats()
    handles = [engine.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    engine.run_until_idle()
    wall = time.perf_counter() - t0
    assert all(h.done() for h in handles)
    snap = engine.snapshot()
    return {
        "wall_s": wall,
        "completed": snap["counters"]["completed"],
        "tokens_out": snap["counters"]["tokens_out"],
        "snapshot": snap,
    }


def _sequential(module: GPT, params, prompts: list) -> dict:
    """The A/B baseline: one-at-a-time static-path generate() —
    compiled once for the shared shape, warmed before timing."""
    fn = jax.jit(
        lambda p, pr: generate(module, p, pr, max_new_tokens=MAX_NEW)
    )
    prompt0 = jnp.asarray([prompts[0]], jnp.int32)
    jax.block_until_ready(fn(params, prompt0))  # compile
    t0 = time.perf_counter()
    for p in prompts:
        jax.block_until_ready(fn(params, jnp.asarray([p], jnp.int32)))
    wall = time.perf_counter() - t0
    return {"wall_s": wall,
            "requests_per_sec": len(prompts) / wall,
            "tokens_per_sec": len(prompts) * MAX_NEW / wall}


def _poisson_arm(engine: ServeEngine, prompts: list, rate_rps: float,
                 seed: int) -> dict:
    """Open loop: submit on a seeded exponential arrival schedule while
    the engine thread serves, then wait for the tail."""
    import random

    engine.stats = ServeStats()
    rng = random.Random(seed)
    handles = []
    t0 = time.perf_counter()
    next_t = 0.0
    for p in prompts:
        next_t += rng.expovariate(rate_rps)
        lag = t0 + next_t - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        handles.append(engine.submit(p, MAX_NEW))
    deadline = time.perf_counter() + 120
    for h in handles:
        h._done.wait(max(0.0, deadline - time.perf_counter()))
    # Drain stragglers of an overloaded arm INTO THIS ARM's stats —
    # the caller swaps engine.stats next, and a request finishing after
    # the swap would corrupt the next arm's completed/latency numbers.
    while engine.scheduler.has_work():
        if time.perf_counter() > deadline + 60:
            sys.stderr.write(
                "bench_serve: rate arm failed to drain within its "
                "deadline — sweep numbers for later arms are suspect\n"
            )
            break
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    snap = engine.snapshot()
    return {
        "offered_rps": round(rate_rps, 3),
        "requests_per_sec": round(snap["counters"]["completed"] / wall, 3),
        "p50_token_latency_ms": _lat(snap, "token", "p50_ms"),
        "p99_token_latency_ms": _lat(snap, "token", "p99_ms"),
        "p50_ttft_ms": _lat(snap, "ttft", "p50_ms"),
        "p99_ttft_ms": _lat(snap, "ttft", "p99_ms"),
        "completed": snap["counters"]["completed"],
        "expired": snap["counters"]["expired"],
        "rejected": snap["counters"]["rejected"],
    }


def _spec_arm(target, target_params, serve_cfg: ServeConfig,
              prompts: list, draft=None, draft_params=None) -> dict:
    """One closed-loop pass on a fresh engine: warmup (compiles), then
    the timed saturating load with the recompile counter pinned."""
    eng = ServeEngine(
        target, target_params, serve_cfg,
        draft_module=draft, draft_params=draft_params,
    )
    for p in prompts[:2]:
        eng.generate(p, SPEC_MAX_NEW)
    eng.stats = ServeStats()
    before = compile_event_count()
    handles = [eng.submit(p, SPEC_MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    assert all(h.done() for h in handles)
    snap = eng.snapshot()
    counters = snap["counters"]
    drafted = counters.get("spec_drafted", 0)
    return {
        "tokens": [h.result(0) for h in handles],
        "tokens_per_sec": counters["tokens_out"] / wall,
        "recompiles": int(compile_event_count() - before),
        "acceptance_rate": (
            counters.get("spec_accepted", 0) / drafted if drafted else None
        ),
        "drafted": drafted,
        "accepted": counters.get("spec_accepted", 0),
        "emitted": counters.get("spec_emitted", 0),
    }


def _spec_block(on_tpu: bool) -> dict:
    """The speculative-decoding A/B: draft + identity-tail target pair,
    spec vs non-spec closed loop, then the acceptance-rate sweep."""
    spec_k = int(os.environ.get("RLT_SPEC_K", "4") or 4)
    if on_tpu:
        draft_cfg = GPTConfig(vocab_size=50304, n_layer=2, n_head=12,
                              d_model=768, seq_len=1024, warmup_steps=10)
        n_extra, serve_cfg = 10, ServeConfig(num_slots=16, block_size=32,
                                             spec_k=spec_k)
    else:
        # Same weight-streaming-regime sizing rationale as the headline
        # arm: the 2-layer draft is ~1/6 the per-token weight traffic
        # of the 12-layer target, which is where drafting pays — a
        # tiny-draft/large-target pair, not two near-equals.
        draft_cfg = GPTConfig(vocab_size=512, n_layer=2, n_head=8,
                              d_model=512, seq_len=128, warmup_steps=2)
        n_extra, serve_cfg = 10, ServeConfig(num_slots=8, block_size=16,
                                             spec_k=spec_k)
    draft = GPT(draft_cfg, attn_impl="auto")
    if on_tpu:
        draft.precision = "bf16"
    draft_params = draft.init_params(jax.random.PRNGKey(0))
    target, target_params = pad_identity_layers(
        draft, draft_params, n_extra
    )
    prompts = _prompts(SPEC_REQUESTS, draft_cfg.vocab_size, seed=42)
    base_cfg = ServeConfig(num_slots=serve_cfg.num_slots,
                           block_size=serve_cfg.block_size)
    baseline = _spec_arm(target, target_params, base_cfg, prompts)
    spec = _spec_arm(target, target_params, serve_cfg, prompts,
                     draft=draft, draft_params=draft_params)
    sweep = []
    for noise in SPEC_NOISE_SWEEP:
        noisy, noisy_params = pad_identity_layers(
            draft, draft_params, n_extra, noise=noise
        )
        arm = _spec_arm(noisy, noisy_params, serve_cfg, prompts,
                        draft=draft, draft_params=draft_params)
        # The perturbed target costs exactly the clean target's compute
        # (same shapes, different values), so the clean baseline arm is
        # the denominator for every sweep point.
        sweep.append({
            "noise": noise,
            "acceptance_rate": round(arm["acceptance_rate"], 4),
            "tokens_per_sec": round(arm["tokens_per_sec"], 1),
            "vs_baseline": round(
                arm["tokens_per_sec"] / baseline["tokens_per_sec"], 3
            ),
        })
    return {
        "spec_k": spec_k,
        "draft_layers": draft_cfg.n_layer,
        "target_layers": draft_cfg.n_layer + n_extra,
        "tokens_per_sec": round(spec["tokens_per_sec"], 1),
        "baseline_tokens_per_sec": round(baseline["tokens_per_sec"], 1),
        "vs_baseline": round(
            spec["tokens_per_sec"] / baseline["tokens_per_sec"], 3
        ),
        "acceptance_rate": round(spec["acceptance_rate"], 4),
        "recompiles_steady_state": spec["recompiles"],
        "baseline_recompiles_steady_state": baseline["recompiles"],
        "drafted": spec["drafted"],
        "accepted": spec["accepted"],
        "emitted": spec["emitted"],
        "greedy_parity": spec["tokens"] == baseline["tokens"],
        "requests": SPEC_REQUESTS,
        "max_new_tokens": SPEC_MAX_NEW,
        "acceptance_sweep": sweep,
    }


DISAGG_REQUESTS = 24
DISAGG_CHAOS_REQUESTS = 24


def _fleet_recompiles(router, ids, timeout=15.0) -> dict:
    """Per-replica compile-event counters from FRESH beats: wait out at
    least one beat interval so the reading postdates the work being
    measured, then require a recent beat from every queried replica."""
    time.sleep(0.6)  # > 2 beat intervals at the fleet default 0.25s
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        snap = router.snapshot()
        entries = {r["id"]: r for r in snap["replicas"]
                   if r["id"] in ids and r.get("alive")}
        if len(entries) == len(ids) and all(
            "recompiles" in e
            and e.get("last_beat_age_s") is not None
            and e["last_beat_age_s"] < 1.0
            for e in entries.values()
        ):
            return {rid: e["recompiles"] for rid, e in entries.items()}
        time.sleep(0.1)
    snap = router.snapshot()
    return {r["id"]: r.get("recompiles", 0) for r in snap["replicas"]
            if r["id"] in ids}


def _disagg_poisson(client, prompts, rate_rps, seed,
                    kill_at=None, kill_fn=None):
    """Open-loop Poisson submission through the router; returns
    (rids, killed_at_index).  ``kill_fn`` fires once after the
    ``kill_at``-th submission — the mid-sweep chaos trigger."""
    import random

    rng = random.Random(seed)
    rids = []
    t0 = time.perf_counter()
    next_t = 0.0
    killed = None
    for i, p in enumerate(prompts):
        next_t += rng.expovariate(rate_rps)
        lag = t0 + next_t - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        rids.append(client.submit(p, MAX_NEW))
        if kill_fn is not None and killed is None and i + 1 >= kill_at:
            kill_fn()
            killed = i
    return rids, killed


def _disagg_block(module, params, serve_cfg, monolith_rps,
                  cfg) -> dict:
    """Phase 5: the disaggregated fleet A/B + kill-a-replica chaos."""
    from ray_lightning_tpu.serve.client import ServeClient
    from ray_lightning_tpu.serve.dist import launch_actor_fleet

    n_replicas = int(os.environ.get("RLT_DISAGG_REPLICAS", "2") or 2)
    n_prefill = int(os.environ.get("RLT_DISAGG_PREFILL", "1") or 1)
    fleet = launch_actor_fleet(
        module, params, serve_cfg, n_replicas=n_replicas,
        n_prefill=n_prefill, lost_after_s=2.0,
    )
    client = ServeClient(fleet.queue_handle())
    replica_ids = [r.id for r in fleet.replicas]
    try:
        # Warmup: every replica compiles its bucket prefill/import +
        # decode programs (uniform prompt length = one bucket; spread
        # enough requests that least-loaded placement hits them all).
        warm = [client.submit(p, MAX_NEW)
                for p in _prompts(4 * n_replicas, cfg.vocab_size,
                                  seed=100)]
        for rid in warm:
            client.result(rid, timeout=600)
        base_rec = _fleet_recompiles(fleet.router, replica_ids)

        # Headline: open loop at ~0.9x monolith capacity.
        rate = max(0.9 * monolith_rps, 0.5)
        t0 = time.perf_counter()
        rids, _ = _disagg_poisson(
            client, _prompts(DISAGG_REQUESTS, cfg.vocab_size, seed=201),
            rate, seed=21,
        )
        completed = 0
        for rid in rids:
            try:
                client.result(rid, timeout=600)
                completed += 1
            except Exception:  # noqa: BLE001 - counted below
                pass
        wall = time.perf_counter() - t0
        after_rec = _fleet_recompiles(fleet.router, replica_ids)
        recompiles = sum(after_rec.get(r, 0) - base_rec.get(r, 0)
                         for r in replica_ids)
        rps = completed / wall

        # Chaos arm: SIGKILL the busiest replica mid-sweep.
        client.re_emitted_tokens = 0
        survivor_base = dict(after_rec)

        def kill_busiest():
            with fleet.router._lock:
                loads = {r: 0 for r in replica_ids}
                for t in fleet.router._inflight.values():
                    if t.replica in loads:
                        loads[t.replica] += 1
            victim_id = max(loads, key=lambda r: loads[r])
            next(r for r in fleet.replicas
                 if r.id == victim_id).kill(hard=True)
            kill_busiest.victim = victim_id

        t0 = time.perf_counter()
        rids, _ = _disagg_poisson(
            client, _prompts(DISAGG_CHAOS_REQUESTS, cfg.vocab_size,
                             seed=202),
            rate, seed=22,
            kill_at=DISAGG_CHAOS_REQUESTS // 3, kill_fn=kill_busiest,
        )
        chaos_completed, lost = 0, 0
        for rid in rids:
            try:
                client.result(rid, timeout=600)
                chaos_completed += 1
            except Exception:  # noqa: BLE001 - every non-completion is
                # a LOST request; the acceptance bar is zero
                lost += 1
        victim = getattr(kill_busiest, "victim", replica_ids[0])
        survivors = [r for r in replica_ids if r != victim]
        surv_rec = _fleet_recompiles(fleet.router, survivors)
        survivor_recompiles = sum(
            surv_rec.get(r, 0) - survivor_base.get(r, 0)
            for r in survivors
        )
        counters = fleet.router.counters
        detect = fleet.router.last_failover_detect_s
        with fleet.router._lock:
            kv_imports = sum(
                (m.snapshot or {}).get("counters", {}).get(
                    "kv_imports", 0)
                for m in fleet.router._replicas.values()
            )
        return {
            "replicas": n_replicas,
            "prefill_workers": n_prefill,
            "requests": DISAGG_REQUESTS,
            "requests_per_sec": round(rps, 3),
            "monolith_requests_per_sec": round(monolith_rps, 3),
            "vs_monolith": round(rps / monolith_rps, 3),
            "kv_imports": int(kv_imports),
            "prefill_dispatches": counters["prefill_dispatches"],
            "recompiles_steady_state": int(recompiles),
            "chaos": {
                "killed_replica": victim,
                "submitted": DISAGG_CHAOS_REQUESTS,
                "completed": chaos_completed,
                "lost_requests": lost,
                "failed_over_requests":
                    counters["failed_over_requests"],
                "failover_detect_s": (
                    None if detect is None else round(detect, 3)
                ),
                "re_emitted_tokens": client.re_emitted_tokens,
                "survivor_recompiles_steady_state":
                    int(survivor_recompiles),
                "offered_rps": round(rate, 3),
            },
        }
    finally:
        client.close()
        fleet.close()


# Longer generations than the headline arm: the drain has to land
# while the disturbed stream still has decode left to migrate, and the
# failover arm's recompute cost (what migration avoids) scales with
# the tokens already generated at kill time.  The kill lands at 3/4 of
# the stream — the rolling-restart shape, where long-running sequences
# are resident at drain time and recompute-from-zero is at its most
# expensive (inproc members are detected dead instantly via their
# thread handle, so the unplanned arm pays no detection window here;
# recomputed work is the whole difference being measured).
CHAOS_MAX_NEW = 96
CHAOS_KILL_AT = 3 * CHAOS_MAX_NEW // 4


def _serve_chaos_disturb(module, params, serve_cfg, ref, *, hard):
    """One disturbance arm of the migration-vs-failover A/B: launch a
    two-replica inproc fleet, start a sampled stream + a greedy
    companion, take the placed replica down (``hard=False`` = planned
    drain, ``hard=True`` = abrupt death) and measure time-to-recovery as
    kill -> first FRESH token AFTER the router booked the recovery (the
    counter anchor keeps a straggler token already in flight from
    under-measuring TTR).  Returns the arm's booking dict."""
    from ray_lightning_tpu.serve.client import ServeClient
    from ray_lightning_tpu.serve.dist import launch_inproc_fleet

    counter = "failovers" if hard else "migrations"
    fleet = launch_inproc_fleet(
        module, params, serve_cfg, n_replicas=2, n_prefill=0,
        lost_after_s=0.5,
    )
    client = ServeClient(fleet.queue_handle())
    try:
        prompts = _prompts(2, module.config.vocab_size, seed=303)
        r1 = client.submit(prompts[0], CHAOS_MAX_NEW, temperature=0.7)
        r2 = client.submit(prompts[1], CHAOS_MAX_NEW)

        def streaming():
            track = fleet.router._inflight.get(r1)
            return (track is not None and track.replica is not None
                    and len(client._pending[r1].tokens) >= CHAOS_KILL_AT)

        deadline = time.perf_counter() + 60
        while not streaming():
            if time.perf_counter() > deadline:
                raise RuntimeError("disturbed stream never started")
            time.sleep(0.01)
        victim = fleet.router._inflight[r1].replica
        t_kill = time.perf_counter()
        next(r for r in fleet.replicas if r.id == victim).kill(hard=hard)
        deadline = time.perf_counter() + 60
        while fleet.router.counters[counter] < 1:
            if time.perf_counter() > deadline:
                raise RuntimeError(f"router never booked a {counter!r}")
            time.sleep(0.01)
        n_base = len(client._pending[r1].tokens)
        while len(client._pending[r1].tokens) <= n_base:
            if time.perf_counter() > deadline:
                raise RuntimeError("stream never resumed post-recovery")
            time.sleep(0.005)
        ttr = time.perf_counter() - t_kill

        lost = 0
        outs = []
        for rid in (r1, r2):
            try:
                outs.append(client.result(rid, timeout=600))
            except Exception:  # noqa: BLE001 - booked as a lost request
                lost += 1
                outs.append(None)
        parity = all(o is not None and o == r
                     for o, r in zip(outs, ref))
        re_emitted = client.re_emitted_tokens

        # Steady-state pin AFTER recovery: a second wave must replay
        # every compiled program (the one cold kv_import executable is
        # allowed to compile DURING recovery, never after it).
        before = compile_event_count()
        w1 = client.submit(prompts[0], CHAOS_MAX_NEW, temperature=0.7)
        w2 = client.submit(prompts[1], CHAOS_MAX_NEW)
        client.result(w1, timeout=600)
        client.result(w2, timeout=600)
        steady = compile_event_count() - before
        counters = fleet.router.counters
        return {
            "ttr_s": round(ttr, 3),
            "lost": lost,
            "parity": parity,
            "re_emitted": re_emitted,
            "steady": int(steady),
            "migrations": counters["migrations"],
            "failovers": counters["failovers"],
        }
    finally:
        client.close()
        fleet.close()


def _serve_chaos_block(module, params, serve_cfg) -> dict:
    """Phase 10: planned-drain live KV migration vs recompute failover.

    The A/B behind the rolling-restart story: arm A drains the placed
    replica with ``RLT_MIGRATE_ON_DRAIN=1`` (resident KV blocks +
    scheduler position move to the survivor, decode resumes
    mid-sequence, zero recomputed prefill); arm B SIGKILL-style kills
    it (recompute failover, the client dedups re-emitted tokens).  Both
    arms must stream bitwise-identical tokens vs an uninterrupted
    monolith engine — sampled AND greedy — lose nothing, and leave no
    cold executables behind.  The full fault matrix lives in
    ``tools/chaos_serve_sweep.py``; this block pins the headline
    numbers per bench round."""
    ref_eng = ServeEngine(module, params, serve_cfg)
    prompts = _prompts(2, module.config.vocab_size, seed=303)
    ref = (ref_eng.generate(prompts[0], CHAOS_MAX_NEW, temperature=0.7),
           ref_eng.generate(prompts[1], CHAOS_MAX_NEW))
    ref_eng.stop()

    os.environ["RLT_MIGRATE_ON_DRAIN"] = "1"
    try:
        # Unmeasured warmup drain: the survivor's kv_import program
        # compiles on the first migration this process ever runs; pay
        # that once here so the measured arm reports steady-state TTR
        # (compile time is the ledger's to book, not a latency number
        # to smuggle into the A/B).
        _serve_chaos_disturb(module, params, serve_cfg, ref,
                             hard=False)
        mig = _serve_chaos_disturb(module, params, serve_cfg, ref,
                                   hard=False)
    finally:
        os.environ.pop("RLT_MIGRATE_ON_DRAIN", None)
    failover = _serve_chaos_disturb(module, params, serve_cfg, ref,
                                    hard=True)
    return {
        "requests": 4,
        "migrations": mig["migrations"],
        "migration_ttr_s": mig["ttr_s"],
        "failover_ttr_s": failover["ttr_s"],
        # Speedup of the planned path over the unplanned one: drain
        # skips the lost_after_s detection window AND the recomputed
        # prefill, so this must land >= 1.
        "migration_vs_failover": round(
            failover["ttr_s"] / max(mig["ttr_s"], 1e-9), 3
        ),
        "lost_requests": mig["lost"] + failover["lost"],
        "parity": mig["parity"] and failover["parity"],
        "migration_re_emitted_tokens": mig["re_emitted"],
        "failover_re_emitted_tokens": failover["re_emitted"],
        "recompiles_steady_state": mig["steady"] + failover["steady"],
    }


LORA_REQUESTS_PER_TENANT = 2
LORA_MAX_NEW = 16
LORA_RANK = 8


def _lora_tenants(cfg, params, n: int, seed: int = 7):
    """``(adapters, merged)`` for ``n`` synthetic tenants of one base
    (``models/gpt.py::synthetic_lora_adapter``), each tenant's merged
    tree kept as the baseline arm's resident copy — computed OUTSIDE
    any timed section (merging is offline prep in the swap workflow;
    the swap itself — the weight upload — is what the timed arm
    pays)."""
    import dataclasses

    from ray_lightning_tpu.models.gpt import synthetic_lora_adapter

    lora_cfg = dataclasses.replace(cfg, lora_rank=LORA_RANK)
    rng = jax.random.PRNGKey(seed)
    adapters, merged = {}, {}
    for i in range(n):
        rng, ki = jax.random.split(rng)
        adapter, merged_tree = synthetic_lora_adapter(
            params, lora_cfg, ki, scale=0.05
        )
        adapters[f"tenant{i}"] = adapter
        merged[f"tenant{i}"] = jax.tree.map(np.asarray, merged_tree)
    return adapters, merged


def _multi_lora_block(module, params, serve_cfg: ServeConfig) -> dict:
    """Phase 7: N-tenant multiplexed pool vs merge-and-swap baseline."""
    n = int(os.environ.get("RLT_MAX_ADAPTERS", "8") or 8)
    cfg = module.config
    prompts = _prompts(n * LORA_REQUESTS_PER_TENANT, cfg.vocab_size,
                       seed=55)
    adapters, merged = _lora_tenants(cfg, params, n)
    names = sorted(adapters)
    hot = names[-2:] if n > 2 else []       # join through the pool
    preloaded = {k: adapters[k] for k in names if k not in hot}

    # -- multiplexed arm: ONE resident base, mixed-tenant batches -------
    mux_cfg = ServeConfig(
        num_slots=serve_cfg.num_slots, block_size=serve_cfg.block_size,
        max_adapters=n, adapter_rank=LORA_RANK,
        # The closed loop submits every request before the first drain:
        # the admission queue must hold the whole wave or the default
        # bound (64) rejects the tail at the hw sweep's 64 tenants.
        max_queue=max(64, n * LORA_REQUESTS_PER_TENANT),
    )
    eng = ServeEngine(module, params, mux_cfg, adapters=preloaded)
    for p in prompts[:2]:
        eng.generate(p, LORA_MAX_NEW)       # warm every program
    eng.stats = ServeStats()
    before = compile_event_count()
    t0 = time.perf_counter()
    handles: dict = {k: [] for k in names}
    for r in range(LORA_REQUESTS_PER_TENANT):
        for i, name in enumerate(names):
            if name in hot and not eng.adapters.has(name):
                eng.add_adapter(name, adapters[name])   # hot join
            handles[name].append(eng.submit(
                prompts[r * n + i], LORA_MAX_NEW, adapter=name,
            ))
    eng.run_until_idle()
    mux_wall = time.perf_counter() - t0
    mux_recompiles = int(compile_event_count() - before)
    snap = eng.snapshot()
    mux_tokens = snap["counters"]["tokens_out"]
    spread = snap["gauges"]["lora_fairness_spread"]
    impl = eng.adapters.impl
    pool_loads = eng.adapters.loads
    mux_streams = {k: [h.result(0) for h in hs]
                   for k, hs in handles.items()}
    eng.stop()

    # -- merge-and-swap baseline: one tenant resident at a time --------
    base_cfg = ServeConfig(num_slots=serve_cfg.num_slots,
                           block_size=serve_cfg.block_size)
    beng = ServeEngine(module, params, base_cfg)
    for p in prompts[:2]:
        beng.generate(p, LORA_MAX_NEW)      # warm the shared programs
    beng.stats = ServeStats()
    before = compile_event_count()
    t0 = time.perf_counter()
    base_streams: dict = {}
    for i, name in enumerate(names):
        # The swap: tenant k's merged copy becomes the resident model
        # (same shapes/dtypes — weights are operands, so no recompile;
        # the cost is the upload plus losing cross-tenant batching).
        beng.params = jax.device_put(merged[name])
        hs = [beng.submit(prompts[r * n + i], LORA_MAX_NEW)
              for r in range(LORA_REQUESTS_PER_TENANT)]
        beng.run_until_idle()
        base_streams[name] = [h.result(0) for h in hs]
    base_wall = time.perf_counter() - t0
    base_recompiles = int(compile_event_count() - before)
    base_tokens = beng.stats.counters["tokens_out"]
    beng.stop()

    parity = all(mux_streams[k] == base_streams[k] for k in names)
    return {
        "adapters": n,
        "rank": LORA_RANK,
        "requests": n * LORA_REQUESTS_PER_TENANT,
        "max_new_tokens": LORA_MAX_NEW,
        "tokens_per_sec": round(mux_tokens / mux_wall, 1),
        "baseline_tokens_per_sec": round(base_tokens / base_wall, 1),
        "vs_baseline": round(
            (mux_tokens / mux_wall) / (base_tokens / base_wall), 3
        ),
        "fairness_spread": round(float(spread), 4),
        "recompiles_steady_state": mux_recompiles,
        "baseline_recompiles_steady_state": base_recompiles,
        "greedy_parity": parity,
        "hot_adds": len(hot),
        "pool_loads": int(pool_loads),
        "bgmv_impl": impl,
        "completed": n * LORA_REQUESTS_PER_TENANT,
    }


PREFIX_REQUESTS = 16
PREFIX_MAX_NEW = 8
PREFIX_SHARED_BLOCKS = 6    # shared system-prompt prefix, whole blocks
PREFIX_UNIQUE_BLOCKS = 1    # per-request unique tail


def _prefix_prompts(cfg, block_size: int, seed: int = 91,
                    share_pct: int = 100) -> tuple:
    """A shared-prefix request mix: ``share_pct``% of the prompts are
    the SAME ``PREFIX_SHARED_BLOCKS``-block system prefix followed by
    a unique one-block tail — the many-users-one-system-prompt shape
    prefix caching exists for — and the rest are fully unique
    same-length prompts (cache misses by construction).
    ``RLT_PREFIX_SHARE`` sweeps this axis on hardware sessions.
    Returns ``(prompts, prefix_share)`` with the share measured in
    TOKENS across the whole mix."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(
        1, cfg.vocab_size, size=(PREFIX_SHARED_BLOCKS * block_size,)
    ).tolist()
    total = (PREFIX_SHARED_BLOCKS + PREFIX_UNIQUE_BLOCKS) * block_size
    carriers = max(1, round(PREFIX_REQUESTS * share_pct / 100))
    prompts = [
        shared + rng.integers(
            1, cfg.vocab_size,
            size=(PREFIX_UNIQUE_BLOCKS * block_size,),
        ).tolist()
        if i < carriers else
        rng.integers(1, cfg.vocab_size, size=(total,)).tolist()
        for i in range(PREFIX_REQUESTS)
    ]
    share = carriers * len(shared) / (PREFIX_REQUESTS * total)
    return prompts, share


def _prefix_arm(module, params, serve_cfg: ServeConfig, prompts: list,
                prefix_on: bool) -> dict:
    """One sequential closed loop on a fresh engine (one request in
    flight at a time, so TTFT is the prefill path and nothing else).
    Warmup covers every program the arm uses — the full-bucket prefill
    AND (cache arm) the suffix chunk program plus a resident chain —
    then the recompile counter is pinned across the timed pass."""
    eng = ServeEngine(module, params, ServeConfig(
        num_slots=serve_cfg.num_slots, block_size=serve_cfg.block_size,
        prefix_cache=prefix_on,
    ))
    try:
        # Two warm requests sharing the mix's prefix: the first
        # compiles the cold full-bucket prefill (and seeds the chain),
        # the second compiles the claimed-suffix program on the cache
        # arm.  Distinct tails keep them out of the measured set.
        rng = np.random.default_rng(977)
        tail = len(prompts[0]) - PREFIX_SHARED_BLOCKS * serve_cfg.block_size
        for _ in range(2):
            warm = prompts[0][: PREFIX_SHARED_BLOCKS
                              * serve_cfg.block_size]
            warm += rng.integers(1, module.config.vocab_size,
                                 size=(tail,)).tolist()
            eng.generate(warm, PREFIX_MAX_NEW)
        eng.stats = ServeStats()
        before = compile_event_count()
        tokens = []
        t0 = time.perf_counter()
        for p in prompts:
            h = eng.submit(p, PREFIX_MAX_NEW)
            eng.run_until_idle()
            tokens.append(h.result(0))
        wall = time.perf_counter() - t0
        recompiles = int(compile_event_count() - before)
        snap = eng.snapshot()
        return {
            "tokens": tokens,
            "wall_s": wall,
            "tokens_per_sec": snap["counters"]["tokens_out"] / wall,
            "ttft_p50_ms": _lat(snap, "ttft", "p50_ms"),
            "recompiles": recompiles,
            "prefix": snap.get("prefix"),
            "prefill_chunks": snap["counters"].get("prefill_chunks", 0),
        }
    finally:
        eng.stop()


def _prefix_cache_block(module, params, serve_cfg: ServeConfig,
                        cfg) -> dict:
    """Phase 8: prefix-aware KV reuse A/B — the same shared-prefix mix
    through a cache-on and a cache-off engine.  The cache arm claims
    the resident prefix by refcount and prefills only the unique tail;
    the headline is the TTFT win, with both arms' steady-state
    recompile counters pinned and bitwise token parity required."""
    share_pct = int(os.environ.get("RLT_PREFIX_SHARE", "100") or 100)
    prompts, share = _prefix_prompts(cfg, serve_cfg.block_size,
                                     share_pct=share_pct)
    cached = _prefix_arm(module, params, serve_cfg, prompts, True)
    baseline = _prefix_arm(module, params, serve_cfg, prompts, False)
    pstats = cached["prefix"] or {}
    return {
        "prefix_share": round(share, 4),
        "requests": PREFIX_REQUESTS,
        "max_new_tokens": PREFIX_MAX_NEW,
        "hit_rate": pstats.get("hit_rate", 0.0),
        "blocks_claimed": int(pstats.get("blocks_claimed", 0)),
        "blocks_inserted": int(pstats.get("blocks_inserted", 0)),
        "cached_blocks": int(pstats.get("cached_blocks", 0)),
        "prefill_chunks": int(cached["prefill_chunks"]),
        "ttft_p50_ms": cached["ttft_p50_ms"],
        "baseline_ttft_p50_ms": baseline["ttft_p50_ms"],
        "ttft_speedup": round(
            baseline["ttft_p50_ms"] / cached["ttft_p50_ms"], 3
        ),
        "tokens_per_sec": round(cached["tokens_per_sec"], 1),
        "baseline_tokens_per_sec": round(
            baseline["tokens_per_sec"], 1
        ),
        "recompiles_steady_state": cached["recompiles"],
        "baseline_recompiles_steady_state": baseline["recompiles"],
        "token_parity": cached["tokens"] == baseline["tokens"],
    }


TRACE_REQUESTS = 24
TRACE_AB_REQUESTS = 24


def _trace_block(module, params, serve_cfg, cfg) -> dict:
    """Phase 6: stitch coverage on an inproc disagg fleet + the
    tracing-overhead A/B on a monolith engine."""
    import shutil
    import tempfile

    from ray_lightning_tpu.serve.client import ServeClient
    from ray_lightning_tpu.serve.dist import launch_inproc_fleet
    from ray_lightning_tpu.telemetry import trace_collect

    # -- overhead A/B: traced vs untraced closed loop ---------------------
    # ONE engine, toggling its tracer flag between passes: identical
    # programs, pool, and allocation history, so the delta is EXACTLY
    # the instrumentation cost.  (Two separate engines measure their
    # own construction-order memory-placement skew — observed ~10% on
    # this container, an order of magnitude above the tracing signal.)
    # The headline is the MEDIAN of adjacent alternating-pair deltas
    # (see the comment at the pair loop); min-wall per arm feeds only
    # the informational rps fields.
    prompts = _prompts(TRACE_AB_REQUESTS, cfg.vocab_size, seed=77)

    def closed_wall(eng):
        eng.stats = ServeStats()
        handles = [eng.submit(p, MAX_NEW) for p in prompts]
        t0 = time.perf_counter()
        eng.run_until_idle()
        wall = time.perf_counter() - t0
        assert all(h.done() for h in handles)
        return wall

    trace_tmp = tempfile.mkdtemp(prefix="rlt_trace_ab_")
    eng = ServeEngine(module, params, serve_cfg, trace_dir=trace_tmp)
    try:
        for p in prompts[:2]:
            eng.generate(p, MAX_NEW)      # warm every program
        closed_wall(eng)                  # one untimed shakeout pass
        # Adjacent pairs with alternating order, MEDIAN of per-pair
        # deltas: the container's throughput drifts for tens of
        # seconds after phase 5's actor teardown, and a min-per-arm
        # over interleaved rounds reads that monotone drift as a
        # multi-percent phantom speedup; per-pair deltas see only the
        # drift ACROSS one adjacent pair, and alternating the order
        # flips its sign pair to pair.
        deltas = []
        base_wall = traced_wall = None
        for pair in range(6):
            order = (False, True) if pair % 2 == 0 else (True, False)
            walls = {}
            for traced in order:
                eng.tracer.enabled = traced
                walls[traced] = closed_wall(eng)
            deltas.append(
                100.0 * (walls[True] - walls[False]) / walls[False]
            )
            base_wall = (walls[False] if base_wall is None
                         else min(base_wall, walls[False]))
            traced_wall = (walls[True] if traced_wall is None
                           else min(traced_wall, walls[True]))
        deltas.sort()
        overhead_pct = deltas[len(deltas) // 2]
        eng.tracer.enabled = True  # export a real trace at stop
    finally:
        eng.stop()
        shutil.rmtree(trace_tmp, ignore_errors=True)

    # -- stitch coverage: traced inproc disagg fleet ----------------------
    stitch_tmp = tempfile.mkdtemp(prefix="rlt_trace_stitch_")
    try:
        # lost_after_s effectively OFF: this phase runs right after the
        # actor-fleet teardown, and an inproc member's beat thread
        # starving past the 1s default would read as a death — the
        # router's (correct) direct-submission fallback would then
        # drop handoff legs from the committed phase chains.
        fleet = launch_inproc_fleet(
            module, params, serve_cfg, n_replicas=2, n_prefill=1,
            lost_after_s=30.0, trace_dir=stitch_tmp,
        )
        client = ServeClient(fleet.queue_handle())
        try:
            rids = [client.submit(p, MAX_NEW)
                    for p in _prompts(TRACE_REQUESTS, cfg.vocab_size,
                                      seed=78)]
            for rid in rids:
                client.result(rid, timeout=600)
            # Completions land router-side on the next beat; the root
            # "request" spans the coverage check counts are recorded
            # there.
            deadline = time.perf_counter() + 10
            while (fleet.router.snapshot()["counters"]["completed"]
                   < TRACE_REQUESTS
                   and time.perf_counter() < deadline):
                time.sleep(0.05)
        finally:
            client.close()
            fleet.close()  # members export their span JSONL here
        spans = trace_collect.load_trace_dir(stitch_tmp)
        complete, total, frac = trace_collect.coverage(spans)
        phases = trace_collect.phase_percentiles(spans)
        sys.stderr.write(
            trace_collect.format_report(spans, slowest_k=3) + "\n"
        )
    finally:
        shutil.rmtree(stitch_tmp, ignore_errors=True)

    return {
        "coverage": round(frac, 4),
        "requests": TRACE_REQUESTS,
        "complete_chains": complete,
        "spans": len(spans),
        "overhead_pct": round(overhead_pct, 3),
        "traced_requests_per_sec": round(
            len(prompts) / traced_wall, 3
        ),
        "baseline_requests_per_sec": round(
            len(prompts) / base_wall, 3
        ),
        "replicas": 2,
        "prefill_workers": 1,
        "phases": phases,
    }


SLO_ARM_S = 10.0            # wall-clock per Poisson alert arm
# Longer passes + more pairs than the tracing A/B: the plane's true
# cost is a few per-export-tick dict folds, so per-pass wall noise —
# not the effect — is what the median has to beat.
SLO_AB_REQUESTS = 48
SLO_AB_PAIRS = 8
# Serving-horizon window pairs for the bench arms: the stock
# minutes-scale defaults would dilute a 10 s overload arm into noise.
SLO_BENCH_WINDOWS = ((1.0, 4.0, 6.0), (2.0, 8.0, 3.0))


def _slo_block(module, params, serve_cfg: ServeConfig, cfg,
               cont_rps: float) -> dict:
    """Phase 9: SLO & capacity-oracle calibration (the ``slo`` block,
    ``validate_bench_slo``).  A fresh plane-on engine serves a cold
    (0.5x capacity) Poisson arm — from which the headroom oracle must
    PREDICT the saturation knee before ever seeing overload — then a
    hot (1.5x) arm measures the real knee and must trip the burn-rate
    alert the cold arm kept silent.  The overhead A/B rides a second
    engine toggling the plane between closed-loop passes (median of
    adjacent alternating-order pairs — the tracing round's
    methodology)."""
    ts_interval = float(
        os.environ.get("RLT_TS_INTERVAL_S", "0.25") or 0.25
    )
    slo_cfg = ServeConfig(
        num_slots=serve_cfg.num_slots, block_size=serve_cfg.block_size,
        capacity=True, slo=True, ts_interval_s=ts_interval,
        export_every_s=ts_interval, slo_windows=SLO_BENCH_WINDOWS,
        # The hot arm holds a standing backlog by design; the queue
        # must absorb it rather than reject (rejections would shed the
        # very overload the alert exists to see).
        max_queue=4096,
    )
    eng = ServeEngine(module, params, slo_cfg)
    oracle = eng.capacity_oracle
    evaluator = eng.slo_evaluator
    # Duration-sized arms: request counts scale with measured capacity
    # so every machine sees ~SLO_ARM_S of sustained load — queue-wait
    # growth under overload is a time-scale effect (backlog grows at
    # 0.5x the service rate, so waits ramp ~0.5 s/s regardless of how
    # fast the chip is), which is what keeps the stock 500 ms bound
    # meaningful across hosts.
    n_cold = max(16, int(0.5 * cont_rps * SLO_ARM_S))
    n_hot = max(24, int(1.5 * cont_rps * SLO_ARM_S))
    cold_prompts = _prompts(n_cold, cfg.vocab_size, seed=311)
    hot_prompts = _prompts(n_hot, cfg.vocab_size, seed=312)
    try:
        for p in cold_prompts[:2]:
            eng.generate(p, MAX_NEW)        # warm every program
        before = compile_event_count()
        eng.start()
        try:
            cold = _poisson_arm(eng, cold_prompts,
                                rate_rps=max(0.5 * cont_rps, 0.5),
                                seed=91)
            alerts_cold = evaluator.alerts_total
            # The oracle calls the knee from cold-arm data alone: the
            # per-slot service rate is load-invariant (each decode tick
            # costs the full width whether 2 or 8 slots are live), so
            # half-load suffices to calibrate the ceiling.
            predicted = oracle.predict_saturation_rps(
                MAX_NEW, window_s=SLO_ARM_S
            )
            hot = _poisson_arm(eng, hot_prompts,
                               rate_rps=max(1.5 * cont_rps, 0.75),
                               seed=92)
            alerts_hot = evaluator.alerts_total - alerts_cold
            hot_cap = oracle.snapshot(window_s=SLO_ARM_S / 2)
        finally:
            eng.stop()
        recompiles = int(compile_event_count() - before)
        ts_points = len(oracle.store.points())
    finally:
        if eng._thread is not None:  # belt: stop() already joined
            eng.stop()
    measured = hot["requests_per_sec"]
    err_pct = None
    if predicted and measured:
        err_pct = 100.0 * abs(predicted - measured) / measured

    # -- overhead A/B: plane on vs off, ONE engine ------------------------
    ab = ServeEngine(module, params, slo_cfg)
    ab_prompts = _prompts(SLO_AB_REQUESTS, cfg.vocab_size, seed=313)
    plane = (ab._capacity, ab._slo)

    def set_plane(on: bool) -> None:
        ab._capacity, ab._slo = plane if on else (None, None)

    def closed_wall() -> float:
        ab.stats = ServeStats()
        handles = [ab.submit(p, MAX_NEW) for p in ab_prompts]
        t0 = time.perf_counter()
        ab.run_until_idle()
        wall = time.perf_counter() - t0
        assert all(h.done() for h in handles)
        return wall

    try:
        for p in ab_prompts[:2]:
            ab.generate(p, MAX_NEW)
        closed_wall()                       # untimed shakeout
        deltas = []
        for pair in range(SLO_AB_PAIRS):
            order = (False, True) if pair % 2 == 0 else (True, False)
            walls = {}
            for on in order:
                set_plane(on)
                walls[on] = closed_wall()
            deltas.append(
                100.0 * (walls[True] - walls[False]) / walls[False]
            )
        deltas.sort()
        overhead_pct = deltas[len(deltas) // 2]
    finally:
        set_plane(True)
        ab.stop()

    return {
        "predicted_saturation_rps": (
            None if predicted is None else round(predicted, 3)
        ),
        "measured_saturation_rps": round(measured, 3),
        "prediction_error_pct": (
            None if err_pct is None else round(err_pct, 2)
        ),
        "alerts_hot": int(alerts_hot),
        "alerts_cold": int(alerts_cold),
        "recompiles_steady_state": recompiles,
        "overhead_pct": round(overhead_pct, 3),
        "capacity_tokens_per_s": hot_cap.get("capacity_tokens_per_s"),
        "service_rate_per_slot": hot_cap.get("service_rate_per_slot"),
        "hot_rps": hot["requests_per_sec"],
        "cold_rps": cold["requests_per_sec"],
        "hot_utilization": hot_cap.get("utilization"),
        "ts_points": ts_points,
    }


def main() -> None:
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, n_layer=12, n_head=12,
                        d_model=768, seq_len=1024, warmup_steps=10)
        serve_cfg = ServeConfig(num_slots=16, block_size=32)
    else:
        # NOT GPTConfig.tiny(): a 1.6 MB-weight model fits in L2, so
        # CPU decode is dispatch-bound and an A/B there measures python
        # overhead, not batching.  ~13M params (~50 MB f32) puts
        # single-token decode in the weight-streaming regime serving
        # actually lives in — each decode step reads every weight once
        # whether it serves 1 token or num_slots of them.
        cfg = GPTConfig(vocab_size=512, n_layer=4, n_head=8,
                        d_model=512, seq_len=128, warmup_steps=2)
        serve_cfg = ServeConfig(num_slots=8, block_size=16)
    module = GPT(cfg, attn_impl="auto")
    if on_tpu:
        module.precision = "bf16"
    params = module.init_params(jax.random.PRNGKey(0))
    engine = ServeEngine(module, params, serve_cfg)
    prompts = _prompts(HEADLINE_REQUESTS, cfg.vocab_size)

    # Phase 1: warmup — compile the bucket + decode programs.
    for p in prompts[:2]:
        engine.generate(p, MAX_NEW)
    compiles_before = compile_event_count()

    # Phase 2: closed-loop headline + sequential A/B.
    closed = _closed_loop(engine, prompts)
    recompiles = compile_event_count() - compiles_before
    seq = _sequential(module, params, prompts)
    cont_rps = closed["completed"] / closed["wall_s"]

    # Phase 3: Poisson rate sweep on the engine thread.
    sweep = []
    engine.start()
    try:
        for i, frac in enumerate(SWEEP_FRACTIONS):
            sweep.append(_poisson_arm(
                engine, _prompts(SWEEP_REQUESTS, cfg.vocab_size,
                                 seed=i + 1),
                rate_rps=max(frac * cont_rps, 0.5), seed=i,
            ))
    finally:
        engine.stop()

    snap = closed["snapshot"]
    serve_block = {
        "requests_per_sec": round(cont_rps, 3),
        "tokens_per_sec": round(
            closed["tokens_out"] / closed["wall_s"], 1
        ),
        "p50_token_latency_ms": _lat(snap, "token", "p50_ms"),
        "p99_token_latency_ms": _lat(snap, "token", "p99_ms"),
        "p50_ttft_ms": _lat(snap, "ttft", "p50_ms"),
        "p99_ttft_ms": _lat(snap, "ttft", "p99_ms"),
        "recompiles_steady_state": int(recompiles),
        "continuous_vs_sequential": round(
            cont_rps / seq["requests_per_sec"], 3
        ),
        "sequential_requests_per_sec": round(
            seq["requests_per_sec"], 3
        ),
        "sequential_tokens_per_sec": round(seq["tokens_per_sec"], 1),
        "num_slots": engine.config.num_slots,
        "block_size": engine.config.block_size,
        "num_blocks": engine.cache.num_blocks,
        "completed": closed["completed"],
        "preempted": snap["counters"]["preempted"],
        "rejected": snap["counters"]["rejected"],
        "expired": snap["counters"]["expired"],
        "rate_sweep": sweep,
    }
    # Phase 4: speculative-decoding A/B + acceptance sweep.
    spec_block = _spec_block(on_tpu)

    # Phase 5: disaggregated fleet A/B + kill-a-replica chaos.
    disagg_block = None
    if int(os.environ.get("RLT_DISAGG_REPLICAS", "2") or 0) > 0:
        disagg_block = _disagg_block(module, params, serve_cfg,
                                     cont_rps, cfg)

    # Phase 6: distributed-tracing stitch coverage + overhead A/B.
    trace_block = _trace_block(module, params, serve_cfg, cfg)

    # Phase 7: multi-tenant LoRA multiplexed vs merge-and-swap A/B.
    multi_lora_block = _multi_lora_block(module, params, serve_cfg)

    # Phase 8: prefix-aware KV reuse A/B (cache on vs off).
    prefix_block = None
    if os.environ.get("RLT_PREFIX_CACHE", "1") != "0":
        prefix_block = _prefix_cache_block(module, params, serve_cfg,
                                           cfg)

    # Phase 9: SLO & capacity-oracle calibration (predict the knee
    # cold, measure it hot, alert only under overload).
    slo_block = None
    if os.environ.get("RLT_SLO", "1") != "0":
        slo_block = _slo_block(module, params, serve_cfg, cfg,
                               cont_rps)

    # Phase 10: planned-drain live migration vs recompute failover A/B.
    chaos_block = None
    if os.environ.get("RLT_SERVE_CHAOS", "1") != "0":
        chaos_block = _serve_chaos_block(module, params, serve_cfg)

    # Compiled-program observatory: by this point every serve plane ran
    # (bucketed prefills, decode, chunked prefill, draft + K+1 verify,
    # LoRA scatter), so the process ledger must hold each steady-state
    # program WITH its cost/memory accounting — the coverage gate below
    # turns a silently-unregistered site into a bench failure.
    from ray_lightning_tpu.telemetry import program_ledger
    from ray_lightning_tpu.telemetry.schema import validate_bench_programs

    ledger_snap = program_ledger.snapshot()
    serve_rows = [r for r in ledger_snap["programs"]
                  if r["site"].startswith("serve/")]
    programs_block = {
        "n_programs": len(serve_rows),
        "compile_time_total_s": round(
            float(ledger_snap["compile_time_total_s"]), 3
        ),
        "recompile_events": len(ledger_snap["recompiles"]),
        # The dispatch-overhead A/B rides bench.py's boring-fit arms;
        # this producer records coverage, not the micro-cost.
        "ledger_overhead_pct": None,
        "rows": serve_rows,
        "hbm": program_ledger.hbm_report(ledger_snap),
    }

    problems = validate_bench_serve(serve_block)
    problems += validate_bench_programs(programs_block)
    for site in ("serve/prefill", "serve/decode", "serve/verify",
                 "serve/lora_scatter"):
        rows = [r for r in serve_rows if r["site"] == site]
        if not rows:
            problems.append(
                f"programs: steady-state serve program {site} missing "
                "from the ledger"
            )
        elif not any("flops" in r and "argument_bytes" in r
                     for r in rows):
            problems.append(
                f"programs: {site} registered without cost+memory rows"
            )
    problems += validate_bench_spec_decode(spec_block)
    problems += validate_bench_trace(trace_block)
    problems += validate_bench_multi_lora(multi_lora_block)
    for arm in ("recompiles_steady_state",
                "baseline_recompiles_steady_state"):
        if multi_lora_block[arm] != 0:
            problems.append(
                f"multi_lora: {arm} = {multi_lora_block[arm]} — the "
                "zero-recompile contract covers adapter joins and "
                "hot-adds in BOTH arms"
            )
    if not multi_lora_block["greedy_parity"]:
        problems.append(
            "multi_lora: multiplexed tenant streams diverged from "
            "their merged-model baselines"
        )
    if trace_block["coverage"] < 0.95:
        problems.append(
            f"trace: stitch coverage {trace_block['coverage']} below "
            "the 0.95 bar"
        )
    if (trace_block["overhead_pct"] is not None
            and trace_block["overhead_pct"] >= 2.0):
        problems.append(
            f"trace: cheap-tier overhead {trace_block['overhead_pct']}% "
            "at or above the 2% bar"
        )
    if prefix_block is not None:
        problems += validate_bench_prefix_cache(prefix_block)
        for arm in ("recompiles_steady_state",
                    "baseline_recompiles_steady_state"):
            if prefix_block[arm] != 0:
                problems.append(
                    f"prefix_cache: {arm} = {prefix_block[arm]} — "
                    "claimed-prefix admissions must replay warmed "
                    "programs in BOTH arms"
                )
        if not prefix_block["token_parity"]:
            problems.append(
                "prefix_cache: cached streams diverged from the "
                "cache-off baseline — shared blocks are not "
                "transparent"
            )
        if prefix_block["hit_rate"] <= 0.0:
            problems.append(
                "prefix_cache: hit_rate 0 under a shared-prefix mix — "
                "the cache never matched"
            )
        # The TTFT bar holds for prefix-heavy mixes (the acceptance
        # shape: >= 50% shared tokens); an RLT_PREFIX_SHARE sweep arm
        # below that measures the hit-rate curve, not the headline.
        if (prefix_block["prefix_share"] >= 0.5
                and prefix_block["ttft_speedup"] < 1.5):
            problems.append(
                f"prefix_cache: ttft_speedup "
                f"{prefix_block['ttft_speedup']} below the 1.5x bar "
                f"at prefix_share {prefix_block['prefix_share']}"
            )
    if disagg_block is not None:
        problems += validate_bench_serve_disagg(disagg_block)
        if disagg_block["chaos"]["lost_requests"]:
            problems.append(
                "serve_disagg.chaos: "
                f"{disagg_block['chaos']['lost_requests']} request(s) "
                "LOST across the replica kill — failover bar is zero"
            )
    if chaos_block is not None:
        problems += validate_bench_serve_chaos(chaos_block)
        if chaos_block["migrations"] < 1:
            problems.append(
                "serve_chaos: planned drain landed no migration frame "
                "— the drain fell back to recompute failover"
            )
        if chaos_block["lost_requests"]:
            problems.append(
                f"serve_chaos: {chaos_block['lost_requests']} "
                "request(s) LOST across the drain/kill arms — the "
                "resilience bar is zero"
            )
        if not chaos_block["parity"]:
            problems.append(
                "serve_chaos: recovered streams diverged from the "
                "uninterrupted monolith reference"
            )
        if chaos_block["migration_re_emitted_tokens"]:
            problems.append(
                "serve_chaos: migration_re_emitted_tokens = "
                f"{chaos_block['migration_re_emitted_tokens']} — a "
                "live migration recomputed prefill"
            )
        if chaos_block["recompiles_steady_state"]:
            problems.append(
                "serve_chaos: recompiles_steady_state = "
                f"{chaos_block['recompiles_steady_state']} — recovery "
                "left cold executables behind in one of the arms"
            )
        if chaos_block["migration_vs_failover"] < 1.0:
            problems.append(
                "serve_chaos: migration TTR "
                f"{chaos_block['migration_ttr_s']}s did not beat "
                f"failover TTR {chaos_block['failover_ttr_s']}s — the "
                "planned path must win"
            )
    if slo_block is not None:
        problems += validate_bench_slo(slo_block)
        if (slo_block["prediction_error_pct"] is None
                or slo_block["prediction_error_pct"] > 20.0):
            problems.append(
                "slo: oracle predicted "
                f"{slo_block['predicted_saturation_rps']} req/s vs "
                f"measured knee {slo_block['measured_saturation_rps']} "
                f"({slo_block['prediction_error_pct']}% error) — "
                "outside the ±20% calibration bar"
            )
        if slo_block["alerts_hot"] < 1:
            problems.append(
                "slo: the 1.5x overload arm fired no burn-rate alert"
            )
        if slo_block["alerts_cold"] != 0:
            problems.append(
                f"slo: {slo_block['alerts_cold']} alert(s) fired in "
                "the 0.5x arm — the burn-rate pager is noisy at "
                "half load"
            )
        if slo_block["recompiles_steady_state"] != 0:
            problems.append(
                "slo: recompiles_steady_state = "
                f"{slo_block['recompiles_steady_state']} with the "
                "plane on — the oracle must be host-side only"
            )
        if (slo_block["overhead_pct"] is not None
                and slo_block["overhead_pct"] >= 2.0):
            problems.append(
                f"slo: plane overhead {slo_block['overhead_pct']}% at "
                "or above the 2% bar"
            )
    if problems:  # the gate that keeps this producer honest
        for p in problems:
            sys.stderr.write(f"bench_serve schema: {p}\n")
        raise SystemExit(1)

    out = {
        "metric": "serve_requests_per_sec"
        if on_tpu else "serve_requests_per_sec_cpu",
        "value": serve_block["requests_per_sec"],
        "unit": "req/s",
        "prompt_len": PROMPT_LEN,
        "max_new_tokens": MAX_NEW,
        "requests": HEADLINE_REQUESTS,
        "serve": serve_block,
        "spec_decode": spec_block,
        "trace": trace_block,
        "multi_lora": multi_lora_block,
        "programs": programs_block,
    }
    if disagg_block is not None:
        out["serve_disagg"] = disagg_block
    if prefix_block is not None:
        out["prefix_cache"] = prefix_block
    if slo_block is not None:
        out["slo"] = slo_block
    if chaos_block is not None:
        out["serve_chaos"] = chaos_block
    print(json.dumps(out))


if __name__ == "__main__":
    main()
