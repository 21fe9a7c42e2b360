"""Sweep fused-CE kernel block sizes on the current backend.

Usage: ``python tools/sweep_ce_blocks.py [--steps 8]``

Times fwd+bwd of the fused LM-head CE at GPT-2-small shapes
(B=16, T=1023, d=768, V=50304) for a grid of (block_t, block_v)
pairs, patching the module constants per trial.  Larger blocks cut the
operand re-streaming (the t-major kernels re-read the full wte per
token block; the v-major dw kernel re-reads x per vocab block) at the
cost of VMEM; compile failures are reported and skipped, not fatal.

Prints one line per config plus the winner; run on real TPU hardware —
on CPU (interpreter) the timings are meaningless and the script exits.
"""

from __future__ import annotations

import argparse
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--bt", type=int, nargs="*", default=[256, 512, 1024])
    ap.add_argument("--bv", type=int, nargs="*", default=[256, 512, 1024])
    args = ap.parse_args()

    if jax.default_backend() != "tpu":
        raise SystemExit(
            "not on TPU — interpreter timings are meaningless"
        )

    from ray_lightning_tpu.ops import cross_entropy as ce

    default_bt, default_bv = ce._CE_BLOCK_T, ce._CE_BLOCK_V
    B, T, d, V = 16, 1023, 768, 50304
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (B, T, d), jnp.bfloat16)
    wte = (jax.random.normal(kw, (V, d), jnp.float32) * 0.02)
    t = jax.random.randint(kt, (B, T), 0, V)

    def loss(x, w):
        return ce.fused_lm_head_cross_entropy(
            x, w, t, use_pallas=True).mean()

    results = []
    for bt, bv in itertools.product(args.bt, args.bv):
        ce._CE_BLOCK_T, ce._CE_BLOCK_V = bt, bv
        try:
            g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            out = g(x, wte)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.steps):
                out = g(x, wte)
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / args.steps * 1e3
            results.append((ms, bt, bv))
            print(f"bt={bt:5d} bv={bv:5d}  {ms:7.2f} ms/step")
        except Exception as e:
            print(f"bt={bt:5d} bv={bv:5d}  FAILED "
                  f"{type(e).__name__}: {str(e)[:90]}")
    if results:
        ms, bt, bv = min(results)
        print(f"best: bt={bt} bv={bv} at {ms:.2f} ms/step "
              f"(current defaults: {default_bt}/{default_bv})")


if __name__ == "__main__":
    main()
