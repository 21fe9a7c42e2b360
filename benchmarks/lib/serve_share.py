"""The closed loop of a family served as one chip's share of an
expert-parallel deployment: set-up, the check against the family's plain
reference, the lead-in by count, the window, the accounting.

``serve_mla_closed`` is this file with its family's names.
``serve_moe_closed`` (PR 26) holds an older copy of the same body, which
a ``benchmark`` PR can move here; its callers' loop is ``serve_closed``'s
and is imported, not copied.

What decides ``correct`` (beside: every request completes, nothing
compiles inside the window).  Each limit lies between two readings on
the chip: what the program gave over its seeds, and what the reference
itself gives when every matmul input is rounded to float8_e4m3, the
nearest precision below the bf16 the configurations state (every run
prints both):

``mean_gap``   a served token's mean distance below the reference's
    argmax at its position, the reference run along the served sequence:
    over the warm-up's tokens, and again with a sample of the requests
    the WINDOW served folded in (the longest and the shortest that were
    taken and finished inside it and fit the reference's compiled
    widths: decode with every slot live, answers of hundreds of tokens,
    blocks that were used before).  The worst single distance is printed.
``logit_rms``   the program's own full-sequence forward on the short
    warm-up sequences: rms of its logits' error over the rms of the
    logits.
``router_score``   the program's sigmoid router scores against the
    reference's in the FIRST routed layer, the only one whose input no
    earlier expert choice has touched.
``flip_margin``   the reference's own margin between its k-th and
    (k+1)-th biased score at a first-layer flip; flips are counted.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Sequence

from benchmarks.drivers.serve_closed import _Caller, _Shared
from benchmarks.drivers.serve_moe_closed import _Taken, _pad_to
from benchmarks.lib import harness, stats, traffic as traffic_lib

LOWPREC = "float8_e4m3fn"
# Reference sequences are padded to one of two widths (causal: the tail
# changes nothing before it), so the float32 layers compile twice; whole
# 128s, so that the program's own forward takes its kernels there.
REF_WIDTH_STEP = 128
# The lead-in ends by count; a system too slow to take its blocks in
# this long opens its window anyway and reports how far it got.
LEAD_IN_CAP_S = 90.0


@dataclasses.dataclass(frozen=True)
class Family:
    """What a driver says of its family."""
    cfg: Any
    module: Any                        # a TpuModule with serve_family()
    ref: Any                           # the reference: config_of, forward
    sequence_forward: Callable         # the program's full-sequence forward
    head_logits: Callable
    selection_bias: Callable[[Any], Any]    # raw params -> (E,) of the
    #                                         first routed layer
    limits: Dict[str, float]           # mean_gap, logit_rms, router_score,
    #                                    flip_margin (the docstring above)
    obs: Dict[str, Any]                # the family's own entries of `obs`


class _Keeping:
    """A ``ServeClient`` for ``_Caller`` whose streams also keep what
    they were served, by the prompt's own list."""

    def __init__(self, client):
        self.client, self.served = client, {}

    def stream(self, prompt, max_new_tokens, **kw):
        out = self.served[id(prompt)] = []
        for token in self.client.stream(prompt, max_new_tokens, **kw):
            out.append(token)
            yield token


def requests_in_order(mix, order_seed, seed, vocab: int, count: int):
    """The mix's requests, their sizes in ``order_seed``'s order and
    their token ids from ``seed``: every seed's window cuts the same
    requests (PERF.md section 6, PR 30: with the order by seed, which
    requests a 45 s window of 2.6 mean request lives holds moves
    ``serve_tokens_per_s`` by more than a new cell's spread may be; the
    two sides of a comparison share a seed, and so an order, either way)."""
    import numpy as np

    rng = np.random.default_rng(int(seed))
    return [dataclasses.replace(
        r, prompt=rng.integers(1, vocab, size=len(r.prompt)).tolist())
        for r in traffic_lib.requests(mix, int(order_seed), vocab, count)]


def reference_check(family: Family, raw_params, served_params,
                    prompts: Sequence[List[int]], served: Sequence[List[int]],
                    widths: Sequence[int], note, phase: str,
                    program: bool = True, gaps_before: Sequence[float] = ()
                    ) -> tuple:
    """``(ok, gaps)``: gaps of every served token under the reference
    (``gaps_before`` counted into the mean); with ``program``, on the
    sequences no longer than ``widths[0]`` the program's own forward
    against the reference (logits, first routed layer's scores and
    choices), and on the longest of those the same readings of a float8
    reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg, ref, module = family.cfg, family.ref, family.module
    rcfg = ref.config_of(cfg)
    k = cfg.top_k
    short_width, long_width = widths
    seqs = {tuple(p + s): (len(p), len(s)) for p, s in zip(prompts, served)}
    short = [q for q in seqs if len(q) <= short_width]

    @jax.jit
    def forward(p, toks):
        routing: list = []
        x, _, _ = family.sequence_forward(
            cfg, p, toks[None], attn_impl=module.attn_impl,
            moe_impl=module.moe_impl, routing=routing)
        return family.head_logits(cfg, p, x)[0], routing

    def against(logits, routing, want, want_routing, bias, n):
        """(logit rms error share, first routed layer: score error,
        flips, worst reference margin at a flip; flips of all layers)."""
        err = np.asarray(logits)[:n] - want[:n]
        rms = float(np.sqrt((err ** 2).mean() / (want[:n] ** 2).mean()))
        flips = []
        for (z, idx), (z_ref, idx_ref) in zip(routing, want_routing):
            flips.append((np.sort(np.asarray(idx)[:n], -1)
                          != np.sort(np.asarray(idx_ref)[:n], -1)).any(-1))
        z, z_ref = (np.asarray(r[0][0])[:n] for r in (routing, want_routing))
        top = -np.sort(-(z_ref + bias), -1)     # the bias selects
        margin = (top[:, k - 1] - top[:, k])[flips[0]]
        return {"logit_rms": rms, "score_err": float(np.abs(z - z_ref).max()),
                "first_layer_flips": int(flips[0].sum()),
                "flip_margin": float(margin.max()) if margin.size else 0.0,
                "flips": int(sum(f.sum() for f in flips)), "rows": n}

    bias = np.asarray(family.selection_bias(raw_params), np.float32)
    gaps: List[float] = []
    readings: List[Dict[str, float]] = []
    low: Dict[str, Any] = {}
    for q, (n_prompt, n_new) in seqs.items():
        toks = np.zeros((short_width if q in short else long_width,),
                        np.int32)
        toks[:len(q)] = q
        toks = jnp.asarray(toks)
        logits, routings = ref.forward(rcfg, raw_params, toks)
        want = np.asarray(logits)
        at = np.arange(n_prompt - 1, n_prompt - 1 + n_new)
        best = want[at].max(-1)
        gaps += [float(g) for g in
                 best - want[at, np.asarray(q[n_prompt:])]]
        if not program or q not in short:
            continue
        got, routing = forward(served_params, toks)
        readings.append(against(
            got, [(z, i) for i, z in routing], want, routings, bias, len(q)))
        if q == max(short, key=len):
            low_logits, low_routings = ref.forward(
                rcfg, raw_params, toks, precision=LOWPREC)
            low = against(low_logits, low_routings, want, routings, bias,
                          len(q))
            low_gaps = best - want[at, np.asarray(low_logits)[at].argmax(-1)]
            low.update(worst_gap=float(low_gaps.max()),
                       mean_gap=float(low_gaps.mean()))
    limits = family.limits
    counted = list(gaps_before) + gaps
    mean_gap = sum(counted) / len(counted)
    ok = all(math.isfinite(g) for g in gaps) and mean_gap <= limits["mean_gap"]
    line: Dict[str, Any] = {}
    if program:
        worst = {key: max(r[key] for r in readings)
                 for key in ("logit_rms", "score_err", "flip_margin")}
        ok = (ok and worst["logit_rms"] <= limits["logit_rms"]
              and worst["score_err"] <= limits["router_score"]
              and worst["flip_margin"] <= limits["flip_margin"])
        line = {"program_forward": {
            **worst, "rows": sum(r["rows"] for r in readings),
            "first_layer_flips": sum(r["first_layer_flips"]
                                     for r in readings),
            "expert_choice_flips": sum(r["flips"] for r in readings)},
            "lowprec": LOWPREC, "lowprec_reference": low}
    note(phase=phase, ok=ok, tokens=len(gaps),
         exact_argmax=sum(g == 0.0 for g in gaps),
         worst_logit_gap=max(gaps), mean_logit_gap=sum(gaps) / len(gaps),
         mean_logit_gap_counted=mean_gap, tokens_counted=len(counted),
         sequence_lengths=sorted(len(q) for q in seqs),
         original_context=getattr(cfg, "rope_original_len", None),
         limits=dict(limits), ref_widths=list(widths), **line)
    return ok, gaps


def window_sample(records, requests, kept: Dict[int, List[int]],
                  window: tuple, longest: int) -> tuple:
    """``(prompts, served)`` of the longest and the shortest request
    that was taken and finished inside ``window`` and is no longer than
    ``longest`` (what the reference has compiled)."""
    t_open, t_close = window
    whole = []
    for r in records:
        prompt = requests[r["index"] % len(requests)].prompt
        tokens = kept.get(id(prompt), [])
        if (r["status"] == "ok" and len(tokens) == r["asked"]
                and t_open <= r["t_submit"] and r["t_done"] <= t_close
                and len(prompt) + len(tokens) <= longest):
            whole.append((len(prompt) + len(tokens), r["index"], prompt,
                          tokens))
    whole.sort(key=lambda w: w[:2])
    picked = whole[-1:] + whole[:1] if len(whole) > 1 else whole
    return [w[2] for w in picked], [w[3] for w in picked]


def run(run: harness.Run, family: Family) -> Dict[str, Any]:
    import jax
    import numpy as np

    from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine
    from ray_lightning_tpu.telemetry.step_stats import (
        compile_event_count, compile_time_total_s,
    )

    device = harness.claim_device(run)
    compile_event_count()                       # arm the listener
    cfg, module = family.cfg, family.module
    mix, system = run.traffic(), run.system()
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
        widths = (
            _pad_to(int(system["reference_short_width"]), REF_WIDTH_STEP),
            _pad_to(max(len(p) for p in prompts) + new, REF_WIDTH_STEP))
        check_ok, gaps = reference_check(
            family, params, engine.params, prompts + prompts,
            served + served2, widths, run.note, "reference_check")
        check_ok = check_ok and all(len(s) == new for s in served + served2)
        run.mark("reference_checked")
        run.note(phase="warmup", warmup_s=time.time() - t_warm,
                 same_tokens_alone_and_batched=sum(
                     a == b for a, b in zip(served, served2)))

        # -- lead-in, then the window ----------------------------------
        n_callers = int(mix["arrivals"]["callers"])
        block = int(mix["block"])
        lead_in = block * int(system["lead_in_blocks"])
        count = lead_in + block * (
            1 + int((run.seconds + LEAD_IN_CAP_S) * 8 / block))
        requests = requests_in_order(
            mix, system["order_seed"], run.seed, vocab, count)
        keeping = _Keeping(client)
        shared = _Shared(keeping, requests, timeout_s)
        shared.counter = counter = _Taken()
        callers = [_Caller(shared) for _ in range(n_callers)]
        for c in callers:
            c.start()
        t_cap = time.perf_counter() + LEAD_IN_CAP_S
        while counter.taken <= lead_in and time.perf_counter() < t_cap:
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

    # -- what the window served, held to the reference (after the window:
    # none of it is set-up) --------------------------------------------
    t_check = time.time()
    sample = window_sample(records, requests, keeping.served,
                           (t_open, t_close), widths[1])
    window_ok = bool(sample[0]) and reference_check(
        family, params, None, *sample, widths, run.note,
        "reference_check_window", program=False, gaps_before=gaps)[0]
    correct = (check_ok and window_ok and compiles_in_window == 0
               and not failed)

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
             order_seed=system["order_seed"],
             window_check_s=time.time() - t_check,
             timeline={**run.timeline,
                       "window_open": round(wall_open - run.t_start, 3)},
             compile_events_in_window=compiles_in_window,
             compile_events_setup=compiles_open,
             compile_s_setup=compile_s_open, setup_s=end_to_end["setup_s"],
             engine_counters_in_window=dc,
             counters_set_at_build={
                 k: v for k, v in counters_close.items()
                 if k in ("latent_row_bytes", "weights_resident_bytes")},
             cache_dir=jax.config.jax_compilation_cache_dir, memory=mem)

    trace = session.load() if session is not None else None
    obs = {
        "trace": trace, "cfg": cfg, "device": device,
        "counters": {**dc, "num_slots": serve_config.num_slots,
                     "compile_s_setup": compile_s_open,
                     "moe_top_k": cfg.top_k},
        "moe": {"d_model": cfg.d_model, "d_expert": cfg.d_expert,
                "itemsize": jax.numpy.dtype(cfg.param_dtype).itemsize},
        **family.obs,
    }
    return harness.finish(
        run, correct=correct, attempted=len(in_window), failed=len(failed),
        end_to_end=end_to_end, obs=obs, device=device, trace=trace)
