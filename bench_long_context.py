"""Long-context attention bench: seq-4096 flash vs ring (zig-zag vs
contiguous).  Prints ONE JSON line.

On real TPU hardware this records the single-chip flash-attention
fwd+bwd number at seq 4096 (the baseline sequence parallelism must beat
at scale).  Multi-chip SP cannot be timed meaningfully in this
environment (one physical chip; the CPU-mesh ring measures thread
scheduling, not ICI) — so the ring layouts are additionally compared by
their *causal work balance*: the max-over-devices count of unmasked
(query, key) block pairs per hop, the quantity that sets ring wall-clock.
Zig-zag's bound is ~half of contiguous — the same 2x the Megatron
context-parallel striped layout reports on hardware.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from ray_lightning_tpu.ops.ring_attention import zigzag_indices  # noqa: F401


def _work_imbalance(n: int, layout: str) -> float:
    """Max-over-devices unmasked attention AREA divided by the perfectly
    balanced share (total causal area / n).  1.0 = ideal; the contiguous
    layout's last device approaches ~2.0 (it owns the final chunk, which
    attends to everything), which is the ring's wall-clock multiplier."""
    if layout == "zigzag":
        chunks = {j: (j, 2 * n - 1 - j) for j in range(n)}
        n_chunks = 2 * n
    else:
        chunks = {j: (j,) for j in range(n)}
        n_chunks = n
    cell = (1.0 / n_chunks) ** 2  # area of one full (qc, kc) chunk pair
    per_dev = []
    for dev in range(n):
        total = 0.0
        for src in range(n):  # one hop per source device
            for qc in chunks[dev]:
                for kc in chunks[src]:
                    if kc < qc:
                        total += cell
                    elif kc == qc:
                        total += cell / 2
        per_dev.append(total)
    ideal = sum(per_dev) / n
    return max(per_dev) / ideal


def _peak_hbm_mb() -> float | None:
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            return round(stats["peak_bytes_in_use"] / 2**20, 1)
    except Exception:  # noqa: BLE001 - not all runtimes expose stats
        pass
    return None


def _time_attn(impl: str, S: int, B: int, H: int, D: int, reps: int = 5):
    """Fwd+bwd wall time for one attention impl at (B, S, H, D); returns
    (ms, tokens_per_sec, peak_hbm_mb) or an 'oom'/error marker string."""
    from ray_lightning_tpu.ops.attention import causal_attention

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    k, v = q * 0.99, q * 1.01

    def fb(q, k, v):
        g = jax.grad(
            lambda q, k, v: causal_attention(q, k, v, impl=impl)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2),
        )(q, k, v)
        return sum(x.astype(jnp.float32).sum() for x in g)

    try:
        f = jax.jit(fb)
        float(jax.device_get(f(q, k, v)))  # compile + one run
        t0 = time.perf_counter()
        for _ in range(reps):
            s = f(q, k, v)
        float(jax.device_get(s))
        dt = (time.perf_counter() - t0) / reps
        return {
            "ms": round(dt * 1000, 2),
            "tokens_per_sec": round(B * S / dt, 1),
            "peak_hbm_mb": _peak_hbm_mb(),
        }
    except Exception as e:  # noqa: BLE001 - OOM at long seq is a finding
        msg = str(e).lower()
        return "oom" if ("resource_exhausted" in msg or "memory" in msg) \
            else f"error: {str(e)[:120]}"


def _one_in_subprocess(impl: str, S: int, B: int, H: int, D: int):
    """Run one (impl, S) measurement in a FRESH process so
    ``peak_bytes_in_use`` (a process-lifetime monotone max) is the peak
    of exactly this config — in-process, every entry after the first
    would inherit the largest earlier peak."""
    import os
    import subprocess
    import sys

    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", impl,
             str(S), str(B), str(H), str(D)],
            capture_output=True, text=True, timeout=1200,
        )
    except subprocess.TimeoutExpired:
        # One slow config (e.g. the O(S^2) XLA arm at 32k) must not
        # discard the measurements already collected.
        return "error: timeout (1200s)"
    # The child prints one backend-tagged JSON dict; failed measurements
    # carry the marker under "result".
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            out = json.loads(line)
        except ValueError:
            continue
        if not isinstance(out, dict):
            continue
        if out.get("backend") != "tpu":
            return f"error: child ran on {out.get('backend')!r}, not tpu"
        out.pop("backend", None)
        return out.get("result", out)
    return f"error: subprocess rc={proc.returncode}: {proc.stderr[-200:]}"


def _child_backend() -> str:
    """``jax.default_backend()`` as a fresh child sees it (the child
    exits, and releases the chip, before this returns)."""
    import os
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--backend"],
        capture_output=True, text=True, timeout=300, check=True,
    )
    return json.loads(proc.stdout.strip().splitlines()[-1])["backend"]


def _chunked_prefill_block() -> dict:
    """Serving-side long-context story: a long prompt admitted against
    RESIDENT decode traffic through chunked prefill
    (``ServeConfig.prefill_chunk``) — one fixed-width chunk per engine
    step interleaved with the decode tick, so the long admission never
    head-of-line-blocks in-flight streams.  CPU-runnable (tiny model;
    the contract being measured is scheduling, not flops).  Emits the
    schema-gated ``chunked_prefill`` block
    (``validate_bench_chunked_prefill``): ``resident_max_stall_ticks``
    is the max consecutive engine steps a resident slot went without
    emitting while the long prompt chunked in — the no-stall bound
    is 1.  ``RLT_PREFILL_CHUNK`` overrides the chunk width (a width
    sweep on real chips: {512, 1024, 2048}); the prompt and positional
    table scale with it so every width measures the same 6-chunk admission shape."""
    import os

    import numpy as np

    from ray_lightning_tpu.models.gpt import GPT, GPTConfig
    from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
    from ray_lightning_tpu.serve.metrics import ServeStats
    from ray_lightning_tpu.telemetry import compile_event_count

    chunk = int(os.environ.get("RLT_PREFILL_CHUNK", "0") or 0) or 64
    prompt_len = 6 * chunk
    seq_len = max(512, 1 << (prompt_len + 128 - 1).bit_length())
    cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=4, d_model=64,
                    seq_len=seq_len, warmup_steps=1)
    module = GPT(cfg, attn_impl="xla")
    params = module.init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(module, params, ServeConfig(
        num_slots=4, block_size=16, prefill_chunk=chunk,
    ))
    rng = np.random.default_rng(3)

    def _short():
        return rng.integers(1, cfg.vocab_size, size=(24,)).tolist()

    long_prompt = rng.integers(1, cfg.vocab_size,
                               size=(prompt_len,)).tolist()
    try:
        # Warm every program the measured pass replays: the short-
        # bucket prefill + decode, and the chunk program (a full
        # chunked admission end to end).
        eng.generate(_short(), 4)
        eng.generate(rng.integers(1, cfg.vocab_size,
                                  size=(prompt_len,)).tolist(), 4)
        eng.stats = ServeStats()
        before = compile_event_count()

        emitted = {0: 0, 1: 0}
        residents = [
            eng.submit(_short(), 64,
                       on_token=lambda idx, tok, i=i: emitted.__setitem__(
                           i, emitted[i] + 1))
            for i in (0, 1)
        ]
        while not all(emitted.values()):    # both resident + decoding
            eng.step()
        first_long = []
        t_submit = time.perf_counter()
        h_long = eng.submit(
            long_prompt, 8,
            on_token=lambda idx, tok: first_long.append(
                time.perf_counter()),
        )
        # Drive until the long prompt's first token lands, tracking how
        # many consecutive steps each resident went token-less.
        stall, max_stall = {0: 0, 1: 0}, 0
        while not first_long:
            seen = dict(emitted)
            eng.step()
            for i in (0, 1):
                stall[i] = 0 if emitted[i] > seen[i] else stall[i] + 1
                max_stall = max(max_stall, stall[i])
        ttft_ms = (first_long[0] - t_submit) * 1e3
        eng.run_until_idle()
        assert h_long.done() and all(h.done() for h in residents)
        chunks = eng.stats.counters.get("prefill_chunks", 0)
        recompiles = int(compile_event_count() - before)
    finally:
        eng.stop()
    return {
        "prompt_len": prompt_len,
        "chunk_width": chunk,
        "chunks": int(chunks),
        "resident_requests": 2,
        "resident_max_stall_ticks": int(max_stall),
        "ttft_ms": round(ttft_ms, 2),
        "tokens_per_sec": None,
        "recompiles_steady_state": recompiles,
    }


def main() -> None:
    import sys

    if len(sys.argv) > 1 and sys.argv[1] == "--backend":
        print(json.dumps({"backend": jax.default_backend()}))
        return
    if len(sys.argv) > 1 and sys.argv[1] == "--one":
        impl, S, B, H, D = sys.argv[2], *map(int, sys.argv[3:7])
        res = _time_attn(impl, S, B, H, D)
        # Always a dict tagged with the backend the child ACTUALLY ran
        # on; the parent refuses anything but "tpu".
        out = res if isinstance(res, dict) else {"result": res}
        out["backend"] = jax.default_backend()
        print(json.dumps(out))
        return

    # One process per chip: this parent must not initialise a backend
    # until every child that needs the chip has exited (a parent that
    # holds it makes them fail or hang).  A throwaway child names the
    # backend; the parent's own in-process arm runs last.
    on_tpu = _child_backend() == "tpu"
    H, D = 12, 64
    result = {
        "metric": "long_context_flash_vs_xla",
        "backend": "tpu" if on_tpu else "cpu",
        # Max-device work / ideal share (1.0 = balanced): the ring's
        # causal wall-clock multiplier per layout, 8-way ring.
        "ring_imbalance_contiguous": round(
            _work_imbalance(8, "contiguous"), 3),
        "ring_imbalance_zigzag": round(_work_imbalance(8, "zigzag"), 3),
    }
    if on_tpu:
        # The O(S·D)-memory flash kernel vs the O(S²) XLA einsum across
        # the long-context sweep (VERDICT r4 next #7).  Batch shrinks
        # with seq so the flash config always fits; an XLA OOM at long
        # seq is itself the datapoint.  One subprocess per entry so each
        # peak-HBM number is isolated.
        sweep = {}
        for S, B in ((4096, 4), (8192, 2), (16384, 1), (32768, 1)):
            sweep[str(S)] = {
                "batch": B,
                "flash": _one_in_subprocess("flash", S, B, H, D),
                "xla": _one_in_subprocess("xla", S, B, H, D),
            }
        result["seq_sweep_fwd_bwd"] = sweep
    # The serving-side long-context arm: chunked prefill vs resident
    # decode traffic (schema-gated; fails the bench on a stall or a
    # steady-state recompile).
    from ray_lightning_tpu.telemetry.schema import (
        validate_bench_chunked_prefill,
    )

    chunked = _chunked_prefill_block()
    problems = validate_bench_chunked_prefill(chunked)
    if chunked["resident_max_stall_ticks"] > 1:
        problems.append(
            f"chunked_prefill: resident stalled "
            f"{chunked['resident_max_stall_ticks']} ticks — the "
            "no-stall bound is 1 chunk tick"
        )
    if chunked["recompiles_steady_state"] != 0:
        problems.append(
            f"chunked_prefill: {chunked['recompiles_steady_state']} "
            "steady-state recompile(s)"
        )
    if problems:
        for p in problems:
            sys.stderr.write(f"bench_long_context schema: {p}\n")
        raise SystemExit(1)
    result["chunked_prefill"] = chunked
    print(json.dumps(result))
    with open("BENCH_LONGCTX.json", "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
