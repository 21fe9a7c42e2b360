"""Driver ``serve_moe_closed``: ``serve_closed``'s closed loop for the
``exaone_moe`` family (``ray_lightning_tpu/models/exaone_moe.py``), held
to ``benchmarks/reference/exaone_moe_ref.py``.

The loop is ``serve_closed``'s own (its callers are imported, not
copied): an in-process ``ServeEngine`` driven through ``ServeClient`` by
N callers, everything timed at the caller.  What differs is set-up, and
where the lead-in ends: the window opens when the callers take the first
request of the traffic's second block, not after a fixed time.  Every
seed offers the same sizes block by block in another order, and a
prefill costs 12 to 190 ms by its bucket, so the window's tokens move
with WHICH requests of a cut block fall inside it; opened on a block's
edge the window holds whole blocks and one cut one instead of two
(PERF.md section 6, PR 26).  Set-up differs so:

* weights are made on the device in bf16 by the module, a layer and an
  expert at a time;
* the engine's programs are warmed by one request per prefill bucket
  the traffic can reach, alone and then all at once;
* what those requests were SERVED is held to the float32 reference run
  along the served sequences (logits, not tokens), and the program's
  router scores and expert choices on the same sequences are held to
  the reference's.

What decides ``correct`` (beside: every request completes, nothing
compiles inside the window).  Each limit lies between two readings on
the chip (PERF.md section 6, PR 26): what the program gave over its
seeds, and what the reference itself gives when every matmul input is
rounded to float8_e4m3, the nearest precision below the bf16 the
configuration states (every run prints both).  bf16 is a coarse
yardstick here: the reference with its matmul inputs rounded to bf16
already differs from itself in float32 by 3.6% of the logits' rms and
flips 4% of the first routed layer's expert choices, which then run
other experts; the program reads 4.0% and 5%.

``MEAN_GAP_TOL``   a served token's mean distance below the reference's
    argmax at its position (the reference run along the served
    sequence).  The worst single distance is printed, not limited: its
    readings nearly meet (0.68 against 0.95-2.1: one near-tie decides).
``LOGIT_RMS_TOL``   the program's own forward (the code the prefill
    runs) on the short warm-up sequences: rms of its logits' error over
    the rms of the reference's logits.
``ROUTER_SCORE_TOL``   the program's sigmoid router scores against the
    reference's in the FIRST routed layer, the only one whose input no
    earlier expert choice has touched.  A choice can only flip where the
    reference's own margin between its k-th and (k+1)-th score is under
    twice the scores' error: ``FLIP_MARGIN_TOL`` holds the largest such
    margin at a first-layer flip, and the flips are counted.  Later
    layers' flips are counted and printed, no more: after a flip the two
    run different experts.
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from typing import Any, Dict, List

from benchmarks.drivers.serve_closed import _Caller, _Shared
from benchmarks.lib import harness, stats, traffic as traffic_lib
from benchmarks.reference import exaone_moe_ref as ref

# Largest reading of the program over 22 seeds / smallest of the float8
# reference over the 5 seeds since its cast saturates (my chip runs,
# PR 26; PERF.md section 6):
MEAN_GAP_TOL = 0.05        # 0.0152 / 0.229
LOGIT_RMS_TOL = 0.14       # 0.0639 / 0.288
ROUTER_SCORE_TOL = 0.08    # 0.0175 / 0.4495
FLIP_MARGIN_TOL = 0.005    # 0.0018 / 0.0172
LOWPREC = "float8_e4m3fn"
# Reference sequences are padded to one of two widths (causal: the tail
# changes nothing before it), so the float32 layers compile twice; whole
# 128s, so that the program's own forward takes its kernels there.
REF_WIDTH_STEP = 128
# The lead-in ends by count; a system too slow to take a block in this
# long opens its window anyway and reports how far it got.
LEAD_IN_CAP_S = 60.0


class _Taken:
    """``_Shared.counter`` that also says how many requests the callers
    have taken so far."""

    def __init__(self):
        self._count, self._lock, self.taken = (
            itertools.count(), threading.Lock(), 0)

    def __next__(self) -> int:
        with self._lock:
            self.taken += 1
            return next(self._count)


def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def reference_check(cfg, params, module, prompts, served, short_width,
                    note) -> bool:
    """Gaps of every served token under the reference; on the sequences
    no longer than ``short_width`` the program's own forward against the
    reference (logits, first routed layer's scores and choices); on the
    longest of those the same readings of a float8 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.exaone_moe import (
        head_logits, sequence_forward,
    )

    rcfg = ref.config_of(cfg)
    k = cfg.top_k
    seqs = {tuple(p + s): (len(p), len(s)) for p, s in zip(prompts, served)}
    long_width = _pad_to(max(len(q) for q in seqs), REF_WIDTH_STEP)
    short = [q for q in seqs if len(q) <= short_width]

    @jax.jit
    def program(p, toks):
        routing: list = []
        x, _, _ = sequence_forward(
            cfg, p, toks[None], attn_impl=module.attn_impl,
            moe_impl=module.moe_impl, routing=routing)
        return head_logits(cfg, p, x)[0], routing

    def against(logits, routing, want, want_routing, n):
        """(logit rms error share, first routed layer: score error,
        flips, worst reference margin at a flip; flips of all layers)."""
        err = np.asarray(logits)[:n] - want[:n]
        rms = float(np.sqrt((err ** 2).mean() / (want[:n] ** 2).mean()))
        flips = []
        for (z, idx), (z_ref, idx_ref) in zip(routing, want_routing):
            flips.append((np.sort(np.asarray(idx)[:n], -1)
                          != np.sort(np.asarray(idx_ref)[:n], -1)).any(-1))
        z, z_ref = (np.asarray(r[0][0])[:n] for r in (routing, want_routing))
        top = -np.sort(-z_ref, -1)
        margin = (top[:, k - 1] - top[:, k])[flips[0]]
        return {"logit_rms": rms, "score_err": float(np.abs(z - z_ref).max()),
                "first_layer_flips": int(flips[0].sum()),
                "flip_margin": float(margin.max()) if margin.size else 0.0,
                "flips": int(sum(f.sum() for f in flips)), "rows": n}

    gaps: List[float] = []
    readings: List[Dict[str, float]] = []
    low: Dict[str, Any] = {}
    for q, (n_prompt, n_new) in seqs.items():
        toks = np.zeros((short_width if q in short else long_width,),
                        np.int32)
        toks[:len(q)] = q
        toks = jnp.asarray(toks)
        logits, routings = ref.forward(rcfg, params, toks)
        want = np.asarray(logits)
        at = np.arange(n_prompt - 1, n_prompt - 1 + n_new)
        best = want[at].max(-1)
        gaps += [float(g) for g in
                 best - want[at, np.asarray(q[n_prompt:])]]
        if q not in short:
            continue
        got, routing = program(params, toks)
        readings.append(against(
            got, [(z, i) for i, z in routing], want, routings, len(q)))
        if q == max(short, key=len):
            low_logits, low_routings = ref.forward(
                rcfg, params, toks, precision=LOWPREC)
            low = against(low_logits, low_routings, want, routings, len(q))
            low_gaps = best - want[at, np.asarray(low_logits)[at].argmax(-1)]
            low.update(worst_gap=float(low_gaps.max()),
                       mean_gap=float(low_gaps.mean()))
    worst = {key: max(r[key] for r in readings)
             for key in ("logit_rms", "score_err", "flip_margin")}
    mean_gap = sum(gaps) / len(gaps)
    ok = (all(math.isfinite(g) for g in gaps)
          and mean_gap <= MEAN_GAP_TOL
          and worst["logit_rms"] <= LOGIT_RMS_TOL
          and worst["score_err"] <= ROUTER_SCORE_TOL
          and worst["flip_margin"] <= FLIP_MARGIN_TOL)
    note(phase="reference_check", ok=ok, tokens=len(gaps),
         exact_argmax=sum(g == 0.0 for g in gaps),
         worst_logit_gap=max(gaps), mean_logit_gap=mean_gap,
         limits={"mean_gap": MEAN_GAP_TOL, "logit_rms": LOGIT_RMS_TOL,
                 "router_score": ROUTER_SCORE_TOL,
                 "flip_margin": FLIP_MARGIN_TOL},
         program_forward={
             **worst, "rows": sum(r["rows"] for r in readings),
             "first_layer_flips": sum(r["first_layer_flips"]
                                      for r in readings),
             "expert_choice_flips": sum(r["flips"] for r in readings)},
         lowprec=LOWPREC, lowprec_reference=low,
         ref_widths=[short_width, long_width])
    return ok


def run(run: harness.Run) -> Dict[str, Any]:
    import jax
    import numpy as np

    from ray_lightning_tpu.models.exaone_moe import ExaoneMoE, ExaoneMoEConfig
    from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine
    from ray_lightning_tpu.telemetry.step_stats import (
        compile_event_count, compile_time_total_s,
    )

    device = harness.claim_device(run)
    compile_event_count()                       # arm the listener
    fields = dict(run.config_fields())
    for key in ("experts_held", "vocab_held", "layer_types", "mlp_types"):
        if fields.get(key) is not None:
            fields[key] = tuple(fields[key])
    cfg = ExaoneMoEConfig(**fields)
    mix, system = run.traffic(), run.system()
    module = ExaoneMoE(cfg, attn_impl=system.get("attn_impl", "auto"),
                       moe_impl=system.get("moe_impl", "auto"))
    params = module.init_params(jax.random.PRNGKey(int(run.seed) % 2**31))
    run.mark("params_dispatched")
    serve_config = ServeConfig(**system["serve_config"])
    engine = ServeEngine(module, params, serve_config).start()
    client = ServeClient(engine.queue_handle())
    run.mark("engine_started")
    timeout_s = float(system.get("request_timeout_s", 120))
    vocab = cfg.n_vocab_held
    callers: List[_Caller] = []
    session = harness.TraceSession(run) if run.trace else None
    try:
        # -- warm-up: one request per prefill bucket, then all at once ----
        t_warm = time.time()
        rng = np.random.default_rng(int(run.seed) + 1)
        new = int(system["warmup_new_tokens"])
        prompts = [rng.integers(1, vocab, size=(n,)).tolist()
                   for n in system["warmup_prompt_lens"]]
        served = [client.result(client.submit(p, new), timeout=1100)
                  for p in prompts]
        run.mark("warmed_one_by_one")
        again = [client.submit(p, new) for p in prompts]
        served2 = [client.result(r, timeout=600) for r in again]
        run.mark("warmed_together")
        check_ok = reference_check(
            cfg, params, module, prompts + prompts, served + served2,
            _pad_to(int(system["reference_short_width"]), REF_WIDTH_STEP),
            run.note) and all(len(s) == new for s in served + served2)
        run.mark("reference_checked")
        run.note(phase="warmup", warmup_s=time.time() - t_warm,
                 same_tokens_alone_and_batched=sum(
                     a == b for a, b in zip(served, served2)))

        # -- lead-in, then the window ----------------------------------
        n_callers = int(mix["arrivals"]["callers"])
        block = int(mix["block"])
        count = block * (2 + int((run.seconds + 30) * 12 / block))
        requests = traffic_lib.requests(mix, run.seed, vocab, count)
        shared = _Shared(client, requests, timeout_s)
        shared.counter = counter = _Taken()
        callers = [_Caller(shared) for _ in range(n_callers)]
        for c in callers:
            c.start()
        # Lead-in: until a caller takes the second block's first request.
        t_cap = time.perf_counter() + LEAD_IN_CAP_S
        while counter.taken <= block and time.perf_counter() < t_cap:
            time.sleep(0.002)
        lead_in_taken = counter.taken
        compiles_open = compile_event_count()
        compile_s_open = compile_time_total_s()
        counters_open = dict(engine.stats.counters)
        t_open, wall_open = time.perf_counter(), time.time()
        t_close = t_open + run.seconds
        if session is not None:
            time.sleep(min(2.0, run.seconds / 4))
            session.start()
            time.sleep(float(system.get("trace_seconds", 5)))
            session.stop()
        time.sleep(max(0.0, t_close - time.perf_counter()))
        counters_close = dict(engine.stats.counters)
        compiles_close = compile_event_count()
        shared.closing.set()
        lateness_s = time.perf_counter() - t_close
        for c in callers:
            c.join(timeout=timeout_s + 30)
        hung = sum(c.is_alive() for c in callers)
    finally:
        if session is not None and session.started and not session.stopped:
            session.stop()
        client.close()
        engine.stop()

    # -- what the callers saw -------------------------------------------
    records = shared.records
    in_window = [r for r in records if t_open <= r["t_submit"] < t_close]
    failed = [r for r in in_window
              if r["status"] != "ok" or len(r["arrivals"]) != r["asked"]]
    all_arrivals = [t for r in records for t in r["arrivals"]]
    tokens_in_window = sum(t_open <= t <= t_close for t in all_arrivals)
    ttft = [1e3 * (r["arrivals"][0] - r["t_submit"])
            for r in in_window if r["arrivals"]]
    itl = [1e3 * g for g in stats.inter_token_gaps(
        (r["arrivals"] for r in records), (t_open, t_close))]
    if hung or not ttft or not itl:
        raise harness.BenchFailure(
            f"{hung} callers hung, {len(ttft)} first tokens, {len(itl)} gaps")
    end_to_end = {
        "serve_tokens_per_s": tokens_in_window / run.seconds,
        "ttft_p95_ms": stats.percentile(ttft, 95),
        "itl_p95_ms": stats.percentile(itl, 95),
        "setup_s": wall_open - run.t_start,
    }
    compiles_in_window = compiles_close - compiles_open
    correct = check_ok and compiles_in_window == 0 and not failed

    mem = harness.memory_report(device)
    dc = {k: counters_close.get(k, 0) - counters_open.get(k, 0)
          for k in counters_close}
    run.note(phase="serve", window_s=run.seconds,
             requests_submitted=len(in_window), requests_failed=len(failed),
             failures=[r["status"] for r in failed][:5],
             requests_completed_total=len(records),
             tokens_in_window=tokens_in_window,
             ttft_ms={"n": len(ttft), "p50": stats.percentile(ttft, 50),
                      "p95": stats.percentile(ttft, 95), "max": max(ttft),
                      "beyond_p95": stats.samples_beyond(len(ttft), 95)},
             itl_ms={"n": len(itl), "p50": stats.percentile(itl, 50),
                     "p95": stats.percentile(itl, 95), "max": max(itl)},
             close_lateness_s=lateness_s, lead_in_requests=lead_in_taken - 1,
             timeline={**run.timeline,
                       "window_open": round(wall_open - run.t_start, 3)},
             compile_events_in_window=compiles_in_window,
             compile_events_setup=compiles_open,
             compile_s_setup=compile_s_open, setup_s=end_to_end["setup_s"],
             engine_counters_in_window=dc,
             cache_dir=jax.config.jax_compilation_cache_dir, memory=mem)

    trace = session.load() if session is not None else None
    obs = {
        "trace": trace, "cfg": cfg, "device": device,
        "counters": {**dc, "num_slots": serve_config.num_slots,
                     "compile_s_setup": compile_s_open,
                     "moe_top_k": cfg.top_k},
        "moe": {"d_model": cfg.d_model, "d_expert": cfg.d_expert,
                "itemsize": jax.numpy.dtype(cfg.param_dtype).itemsize},
    }
    return harness.finish(
        run, correct=correct, attempted=len(in_window), failed=len(failed),
        end_to_end=end_to_end, obs=obs, device=device, trace=trace)
