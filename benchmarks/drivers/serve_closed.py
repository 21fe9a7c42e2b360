"""Driver ``serve_closed``: an in-process ``ServeEngine`` driven through
``ServeClient`` by a closed loop of N callers, each submitting its next
request the moment its last completes.

Set-up: weights made on the device from ``--seed`` in one jitted call;
the engine's programs warmed by one request per prefill bucket the
traffic can reach (those same requests are then held to the plain
reference); a lead-in of load so that the window opens on full slots.
The window: ``--seconds`` of the callers' own clock.  Everything a
caller sees is timed at the caller: ``submit()`` to first token
(TTFT), gaps between token arrivals (ITL), tokens that arrived inside
the window.  After the window the callers stop submitting and requests
in flight finish, so a failed request can be told from a cut one.
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from typing import Any, Dict, List

from benchmarks.lib import harness, stats, traffic as traffic_lib
from benchmarks.reference import gpt2_ref

# A served token is right when it is the float32 reference's argmax at
# its position (the reference run along the served sequence), or sits
# within LOGIT_TIE_TOL of it: the engine computes in bf16 (8 bits of
# mantissa) through 36 layers, and with random weights the two largest
# of 50304 logits (spread ~0.7) often lie closer than bf16 can order.
# Measured on the chip over 14 runs of 128 tokens (my chip runs, PR 23):
# 126 to 128 of 128 tokens are the argmax itself, the worst gap 0.0226.
# The bound is four times that; a token a whole logit-spread below the
# argmax fails.
LOGIT_TIE_TOL = 1e-1


def reference_gaps(cfg, params, prompts: List[List[int]],
                   served: List[List[int]]):
    """For every served token, how far below the reference's argmax it
    sits, the reference's logits taken along the served sequence."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    new = max(len(s) for s in served)
    width = max(len(p) + len(s) for p, s in zip(prompts, served))
    seqs = np.zeros((len(prompts), width), np.int32)
    pos = np.zeros((len(prompts), new), np.int32)
    toks = np.zeros((len(prompts), new), np.int32)
    valid = np.zeros((len(prompts), new), bool)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seqs[i, :len(p) + len(s)] = p + s
        for j, tok in enumerate(s):
            pos[i, j], toks[i, j], valid[i, j] = len(p) + j - 1, tok, True

    @jax.jit
    def gaps(p, seqs, pos, toks):
        logits = gpt2_ref.forward(
            gpt2_ref.from_stacked(p, cfg.n_layer), seqs, cfg.n_head)
        rows = jnp.take_along_axis(logits, pos[..., None], axis=1)
        picked = jnp.take_along_axis(rows, toks[..., None], axis=-1)[..., 0]
        return rows.max(-1) - picked

    out = np.asarray(gaps(params, jnp.asarray(seqs), jnp.asarray(pos),
                          jnp.asarray(toks)))
    return [float(g) for g, v in zip(out.ravel(), valid.ravel()) if v]


class _Caller(threading.Thread):
    """One caller of the closed loop."""

    def __init__(self, shared: "_Shared"):
        super().__init__(name="bench-caller", daemon=True)
        self.shared = shared

    def run(self) -> None:
        sh = self.shared
        while True:
            idx = next(sh.counter)
            if sh.closing.is_set():
                return
            req = sh.requests[idx % len(sh.requests)]
            rec = {"index": idx, "asked": req.max_new_tokens,
                   "prompt_len": len(req.prompt), "arrivals": [],
                   "status": "ok", "t_submit": time.perf_counter()}
            try:
                for _ in sh.client.stream(req.prompt, req.max_new_tokens,
                                          timeout=sh.timeout_s):
                    rec["arrivals"].append(time.perf_counter())
            except Exception as e:  # noqa: BLE001 - a failed request is a datum
                rec["status"] = f"{type(e).__name__}: {e}"[:200]
            rec["t_done"] = time.perf_counter()
            with sh.lock:
                sh.records.append(rec)


class _Shared:
    def __init__(self, client, requests, timeout_s: float):
        self.client, self.requests, self.timeout_s = (
            client, requests, timeout_s)
        self.counter = itertools.count()
        self.closing = threading.Event()
        self.lock = threading.Lock()
        self.records: List[Dict[str, Any]] = []


def run(run: harness.Run) -> Dict[str, Any]:
    import jax
    import numpy as np

    from ray_lightning_tpu.models import GPT, GPTConfig
    from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine
    from ray_lightning_tpu.telemetry.step_stats import (
        compile_event_count, compile_time_total_s,
    )

    device = harness.claim_device(run)
    compile_event_count()                       # arm the listener
    cfg = GPTConfig(**run.config_fields())
    mix, system = run.traffic(), run.system()
    module = GPT(cfg, attn_impl=system.get("attn_impl", "auto"))
    module.precision = system.get("precision", "bf16")
    params = jax.jit(module.init_params)(
        jax.random.PRNGKey(int(run.seed) % 2**31))
    run.mark("params_dispatched")
    serve_config = ServeConfig(**system["serve_config"])
    engine = ServeEngine(module, params, serve_config).start()
    client = ServeClient(engine.queue_handle())
    run.mark("engine_started")
    timeout_s = float(system.get("request_timeout_s", 120))
    callers: List[_Caller] = []
    session = harness.TraceSession(run) if run.trace else None
    try:
        # -- warm-up: one request per prefill bucket, then all at once ----
        t_warm = time.time()
        rng = np.random.default_rng(int(run.seed) + 1)
        new = int(system["warmup_new_tokens"])
        prompts = [rng.integers(1, cfg.vocab_size, size=(n,)).tolist()
                   for n in system["warmup_prompt_lens"]]
        served = [client.result(client.submit(p, new), timeout=1100)
                  for p in prompts]
        run.mark("warmed_one_by_one")
        again = [client.submit(p, new) for p in prompts]
        served2 = [client.result(r, timeout=600) for r in again]
        run.mark("warmed_together")
        gaps = reference_gaps(cfg, params, prompts + prompts,
                              served + served2)
        run.mark("reference_checked")
        check_ok = (all(len(s) == new for s in served + served2)
                    and all(math.isfinite(g) and g <= LOGIT_TIE_TOL
                            for g in gaps))
        run.note(phase="reference_check", ok=check_ok, tokens=len(gaps),
                 exact_argmax=sum(g == 0.0 for g in gaps),
                 worst_logit_gap=max(gaps), mean_logit_gap=sum(gaps) / len(gaps),
                 logit_tie_tol=LOGIT_TIE_TOL,
                 same_tokens_alone_and_batched=sum(
                     a == b for a, b in zip(served, served2)),
                 warmup_s=time.time() - t_warm)

        # -- lead-in, then the window ----------------------------------
        n_callers = int(mix["arrivals"]["callers"])
        block = int(mix["block"])
        count = block * (2 + int((run.seconds + 30) * 12 / block))
        requests = traffic_lib.requests(mix, run.seed, cfg.vocab_size, count)
        shared = _Shared(client, requests, timeout_s)
        callers = [_Caller(shared) for _ in range(n_callers)]
        for c in callers:
            c.start()
        time.sleep(float(system.get("lead_in_s", 3.0)))
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
             close_lateness_s=lateness_s,
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
                     "compile_s_setup": compile_s_open},
    }
    return harness.finish(
        run, correct=correct, attempted=len(in_window), failed=len(failed),
        end_to_end=end_to_end, obs=obs, device=device, trace=trace)
