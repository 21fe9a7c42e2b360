"""Profile one GPT train step on the current backend and rank op costs.

Usage: ``python tools/profile_step.py [--config gpt2_small|tiny] [--steps 6]``

Captures a ``jax.profiler.trace`` around chained jitted steps (see
docs/PERFORMANCE.md "Profiling recipe"), parses the trace's
``trace.json.gz``, and prints the top XLA ops by total self-duration
plus a coarse bucket breakdown (matmul / attention kernels / CE
kernels / layernorm-elementwise / optimizer / copies).

This is the measurement half of the perf loop; bench.py is the score.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# Trace parsing lives in the telemetry subsystem now (shared with
# tools/trace_summary.py); these aliases keep the harness's historical
# local names working.
from ray_lightning_tpu.telemetry.trace_parse import (  # noqa: E402
    collect,
    op_bucket as _bucket,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="gpt2_small",
                    choices=["gpt2_small", "tiny"])
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=0)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    from ray_lightning_tpu.core.module import TrainState
    from ray_lightning_tpu.models.gpt import GPT, GPTConfig
    from ray_lightning_tpu.parallel.step_fns import build_train_step

    on_tpu = jax.default_backend() == "tpu"
    if args.config == "gpt2_small":
        cfg = GPTConfig(vocab_size=50304, n_layer=12, n_head=12,
                        d_model=768, seq_len=1024, warmup_steps=10)
        batch = args.batch_size or 16
    else:
        cfg = GPTConfig.tiny()
        batch = args.batch_size or 8
    module = GPT(cfg, attn_impl="auto", remat=on_tpu)
    module.precision = "bf16"

    params = module.init_params(jax.random.PRNGKey(0))
    tx = module.configure_optimizers()
    state = TrainState.create(params, tx)
    step = build_train_step(module, tx, mesh=None)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, cfg.seq_len + 1)), jnp.int32)
    rng = jax.random.PRNGKey(0)
    batch_d = {"tokens": tokens}

    # Warm up (compile) outside the trace.
    for _ in range(2):
        state, logs = step(state, batch_d, rng)
    float(jax.device_get(logs["loss"]))

    trace_dir = tempfile.mkdtemp(prefix="rlt_profile_")
    t0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        for _ in range(args.steps):
            state, logs = step(state, batch_d, rng)
        loss = float(jax.device_get(logs["loss"]))
    wall = time.perf_counter() - t0
    print(f"# {args.steps} steps in {wall*1e3:.1f} ms "
          f"({wall/args.steps*1e3:.1f} ms/step), loss={loss:.4f}, "
          f"backend={jax.default_backend()}", file=sys.stderr)

    durs = collect(trace_dir)
    total = sum(durs.values())
    buckets: dict = collections.defaultdict(float)
    for name, d in durs.items():
        buckets[_bucket(name)] += d
    print("== buckets (% of op time) ==")
    for b, d in sorted(buckets.items(), key=lambda kv: -kv[1]):
        print(f"{100*d/total:6.2f}%  {d/1e3/args.steps:8.2f} ms/step  {b}")
    print(f"== top {args.top} ops ==")
    for name, d in sorted(durs.items(), key=lambda kv: -kv[1])[:args.top]:
        print(f"{100*d/total:6.2f}%  {d/1e3/args.steps:8.2f} ms/step  "
              f"{name[:90]}")


if __name__ == "__main__":
    main()
