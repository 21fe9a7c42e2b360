#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the trainer's main path once, through the entry points a user
calls, at the full width of GPT-2-small (12x768, vocab 50304, seq 1024,
batch 16, bf16), and checks what comes out by the repo's own means.
One JSON object per line says what ran; the LAST line is the contract's

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reported it in the process that held the chip.
Any failed phase ends the run with a non-zero exit code and no such
line.  Times printed here are not performance records.

    python chip_smoke.py              # one chip: fit (actor, then
                                      # in-process), serve, kernel check
    python chip_smoke.py --chips 4    # four chips: data=4 and ZeRO-3
                                      # fsdp=4 fits against one device

One process per chip: while a worker actor trains, this driver stays off
JAX; its own in-process phases start after the worker has exited.

Rehearsals without a chip are explicit, never a branch taken because no
chip was found (without these arguments a machine with no TPU fails):

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal --size tiny
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearsal --size tiny --chips 4
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import signal
import sys
import time

from ray_lightning_tpu import LocalStrategy, RayShardedStrategy, RayStrategy
from ray_lightning_tpu import Trainer
from ray_lightning_tpu.core.callbacks import Callback
from ray_lightning_tpu.models import GPT, GPTConfig, SyntheticLMDataModule

ROOT_DIR = "rlt_logs/chip_smoke"
TIME_LIMIT_S = 1150          # the contract's 1200 s, with room to stop
LOSS_RTOL = 5e-3             # fit-vs-fit loss agreement (bf16 compute)
LOGIT_TIE_TOL = 5e-2         # serve: a bf16 near-tie of two top logits
# Bounds of the kernel check, from the bf16 cases of tests/test_ops_flash.py
# and tests/test_ops_fused.py:
# flash by that test's own metric, max|a-b| / max(max|b|, 1) < 1e-2;
# LayerNorm (2e-2 there, absolute on O(1) outputs) and CE by
# max|a-b| / max|b|, with the CE loss itself within 5e-2.
KERNEL_TOL = {"flash": 1e-2, "layer_norm": 2e-2, "cross_entropy": 2e-2}
CE_LOSS_ATOL = 5e-2


# Off-TPU no kernel is selected (a rehearsal's fits take these).
XLA_PATHS = {"attention": "xla", "cross_entropy": "scan", "layer_norm": "xla"}


class SmokeFailure(Exception):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def model_config(size: str) -> GPTConfig:
    if size == "full":
        # Published GPT-2-small widths, untouched; the 10-step LR
        # warm-up lets 27 steps show a falling loss.
        return dataclasses.replace(GPTConfig.gpt2_small(), warmup_steps=10)
    return GPTConfig(vocab_size=512, n_layer=2, n_head=4, d_model=256,
                     seq_len=128, warmup_steps=10)


# ---------------------------------------------------------------------------
# The probe: a user-level callback, so it runs wherever the fit runs —
# inside the worker actor or in this process — and its state rides the
# result package back to the driver's copy.
# ---------------------------------------------------------------------------

class Probe(Callback):
    def __init__(self, expect_platform: str, batch_size: int):
        self.expect_platform = expect_platform
        self.batch_size = batch_size
        self.report: dict = {}
        self.losses: list = []      # [micro_step, train_loss]
        self.compiles: list = []    # compile-event count at each hook

    def setup(self, trainer, module, stage):
        import jax

        from ray_lightning_tpu.telemetry import compile_event_count

        devices = jax.devices()
        self.report["pid"] = os.getpid()
        self.report["device"] = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        if devices[0].platform != self.expect_platform:
            raise RuntimeError(
                f"chip_smoke wants platform {self.expect_platform!r}; "
                f"this process holds {self.report['device']}"
            )
        compile_event_count()  # arm the counter before the first step

    def on_train_batch_end(self, trainer, module, logs, batch_idx):
        import jax

        from ray_lightning_tpu.telemetry import compile_event_count

        self.losses.append([
            int(trainer.micro_step),
            float(jax.device_get(logs["train_loss"])),
        ])
        self.compiles.append(compile_event_count())

    def on_fit_end(self, trainer, module):
        import jax

        from ray_lightning_tpu import native
        from ray_lightning_tpu.telemetry import program_ledger

        snap = program_ledger.snapshot()
        programs = {}
        for row in snap["programs"]:
            if row["site"].startswith("train/"):
                text = program_ledger.hlo_text(row["site"]) or ""
                programs[row["site"]] = {
                    "variants": 1 + programs.get(
                        row["site"], {"variants": 0})["variants"],
                    "compile_s": round(row["compile_s"], 2),
                    "mosaic_kernels": text.count("tpu_custom_call"),
                    "collectives": {
                        k: len(re.findall(rf"\b{k}(?:-start)?\(", text))
                        for k in ("all-reduce", "all-gather",
                                  "reduce-scatter")
                    },
                }
        meta = trainer.telemetry.meta
        state = trainer.state
        self.report.update(
            megastep_k=meta.get("megastep"),
            update_sharding=meta.get("update_sharding"),
            kernel_paths=module.kernel_paths(self.batch_size),
            native_library=native.native_available(),
            compile_cache_dir=jax.config.jax_compilation_cache_dir,
            programs=programs,
            ledger_recompiles=len(snap["recompiles"]),
            memory=[
                {k: (d.memory_stats() or {}).get(k)
                 for k in ("peak_bytes_in_use", "bytes_in_use",
                           "bytes_limit")}
                for d in jax.local_devices()
            ],
            shards={"params": _shard_report(state.params),
                    "opt_state": _shard_report(state.opt_state)},
        )

    @property
    def loss_values(self) -> list:
        return [v for _, v in self.losses]

    def state_dict(self):
        return {"report": self.report, "losses": self.losses,
                "compiles": self.compiles}

    def load_state_dict(self, state):
        self.report = state["report"]
        self.losses = state["losses"]
        self.compiles = state["compiles"]


def _shard_report(tree) -> dict:
    """Where a state subtree's bytes live: logical bytes, bytes held per
    device, and the fewest devices any one leaf has shards on."""
    import jax

    per_device: dict = {}
    total = 0
    min_devices = None
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        held = set()
        for shard in leaf.addressable_shards:
            held.add(shard.device.id)
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
        n = len(held)
        min_devices = n if min_devices is None else min(min_devices, n)
    return {"bytes": total, "min_devices_per_leaf": min_devices,
            "bytes_per_device": [per_device[k] for k in sorted(per_device)]}


def recompiles_after_warmup(probe: Probe) -> int:
    """Compile events from the third use of each train path on.  The
    first use compiles the program (the fused one inside the first
    stride, the single-step one inside the first tail step); the second
    compiles the epoch-mean accumulator's scalar ``add``; after that,
    nothing may compile."""
    n_inner = [b[0] - a[0] for a, b in
               zip([[0, 0.0]] + probe.losses, probe.losses)]
    extra, uses = 0, {"fused": 0, "single": 0}
    for i, n in enumerate(n_inner):
        kind = "fused" if n > 1 else "single"
        uses[kind] += 1
        if uses[kind] > 2:
            extra += probe.compiles[i] - probe.compiles[i - 1]
    check(min(uses.values()) >= 3,
          f"too few steps to see a steady state: {uses}")
    return extra


def fit(strategy, cfg, args, steps: int, expect: str, name: str) -> Probe:
    probe = Probe(expect, args.batch)
    trainer = Trainer(
        strategy=strategy, max_epochs=1, max_steps=steps,
        precision="bf16", seed=args.seed, callbacks=[probe],
        default_root_dir=f"{ROOT_DIR}/{name}",
        # The final state comes back through the result package; a
        # 1.5 GB end-of-epoch checkpoint file is not part of the smoke.
        enable_checkpointing=False,
    )
    module = GPT(cfg, attn_impl="auto", remat=True)
    data = SyntheticLMDataModule(cfg, batch_size=args.batch,
                                 num_batches=steps + 1, seed=args.seed)
    t0 = time.time()
    trainer.fit(module, data)
    emit({"phase": f"fit/{name}", "wall_s": round(time.time() - t0, 1),
          "steps": steps, "losses": probe.losses,
          "compile_events": probe.compiles, **probe.report})
    losses = probe.loss_values
    check(probe.report.get("device", {}).get("platform") == expect,
          f"{name}: fit ran on {probe.report.get('device')}")
    check(probe.losses and probe.losses[-1][0] == steps,
          f"{name}: expected {steps} steps, saw {probe.losses}")
    check(all(v == v and abs(v) != float("inf") for v in losses),
          f"{name}: non-finite loss {losses}")
    probe.trainer = trainer
    return probe


def expect_kernels(probe: Probe, want: dict, name: str) -> None:
    got = probe.report["kernel_paths"]
    check(got == want, f"{name}: kernel paths {got}, expected {want}")
    text_kernels = max(
        p["mosaic_kernels"] for p in probe.report["programs"].values()
    )
    check((text_kernels > 0) == (want != XLA_PATHS),
          f"{name}: {text_kernels} Mosaic calls in the compiled step for "
          f"kernel paths {got}")


def close(a, b, rtol=LOSS_RTOL) -> bool:
    return len(a) == len(b) and all(
        abs(x - y) <= rtol * max(abs(x), abs(y)) for x, y in zip(a, b)
    )


# ---------------------------------------------------------------------------
# One chip: fit through an actor, fit in-process, serve, kernel check
# ---------------------------------------------------------------------------

def run_one_chip(args, expect: str) -> dict:
    cfg = model_config(args.size)
    on_tpu = expect == "tpu"
    steps = 27  # three K=8 strides plus three tail single steps
    # TPU defaults pick K=8 themselves; a CPU rehearsal asks for the
    # same stride so that it walks the same control flow.
    megastep = None if on_tpu else 8

    # -- actor path: this driver must stay off JAX throughout ---------------
    actor = fit(RayStrategy(num_workers=1, megastep=megastep), cfg, args,
                steps, expect, "actor")
    import jax._src.xla_bridge as xb

    check(not xb.backends_are_initialized(),
          "the driver initialised a JAX backend during the actor fit")
    check(actor.report["pid"] != os.getpid(), "actor fit ran in-process")
    check(not _alive(actor.report["pid"]),
          f"worker {actor.report['pid']} still alive after teardown")
    check(actor.trainer.state is not None, "no state came back")
    _check_single_chip_fit(actor, on_tpu, "actor")

    # -- in-process path: the worker has exited, the chip is free -----------
    local = fit(LocalStrategy(megastep=megastep), cfg, args, steps, expect,
                "local")
    check(local.report["pid"] == os.getpid(), "local fit left the process")
    _check_single_chip_fit(local, on_tpu, "local")
    a, b = actor.loss_values, local.loss_values
    check(close(a, b), f"actor and in-process losses differ: {a} vs {b}")
    emit({"phase": "fit/agreement", "max_abs_diff":
          max(abs(x - y) for x, y in zip(a, b)), "rtol": LOSS_RTOL})

    serve_phase(cfg, local.trainer.params, args)
    kernel_phase(cfg, args)
    return local.report["device"]


def _alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    return True


def _check_single_chip_fit(probe: Probe, on_tpu: bool, name: str) -> None:
    r = probe.report
    losses = probe.loss_values
    check(losses[-1] < losses[0], f"{name}: loss did not fall: {losses}")
    k = r["megastep_k"]
    check(k == 8, f"{name}: megastep resolved to K={k}, expected 8")
    check(r["update_sharding"] == "off",
          f"{name}: update sharding on a one-device mesh")
    extra = recompiles_after_warmup(probe)
    check(extra == 0 and r["ledger_recompiles"] == 0
          and all(p["variants"] == 1 for p in r["programs"].values()),
          f"{name}: {extra} compile events / {r['ledger_recompiles']} "
          f"ledger recompiles after warm-up; programs {r['programs']}")
    expect_kernels(probe, {"attention": "flash", "cross_entropy": "pallas",
                           "layer_norm": "pallas"} if on_tpu else XLA_PATHS,
                   name)
    for s in r["shards"].values():
        check(s["min_devices_per_leaf"] == 1, f"{name}: shards {s}")


def serve_phase(cfg, params, args) -> None:
    """ServeEngine + ServeClient on the trained weights: every request
    completes, greedy tokens equal ``generate()``'s, nothing recompiles
    in steady state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.generate import generate
    from ray_lightning_tpu.serve import ServeClient, ServeConfig, ServeEngine
    from ray_lightning_tpu.telemetry import compile_event_count

    t0 = time.time()
    module = GPT(cfg, attn_impl="auto")
    module.precision = "bf16"
    params = jax.tree.map(jnp.asarray, params)
    full = args.size == "full"
    lengths = [12, 12, 30, 30, 100, 100, 200, 200] if full else \
        [5, 5, 12, 12, 30, 30, 60, 60]
    new = 16 if full else 8
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=(n,)).tolist()
               for n in lengths]
    engine = ServeEngine(
        module, params,
        ServeConfig(num_slots=8, block_size=32 if full else 16,
                    max_model_len=min(cfg.seq_len, 512)),
        telemetry_dir=f"{ROOT_DIR}/serve",
    ).start()
    client = ServeClient(engine.queue_handle())
    try:
        # Warm-up: one request per prefill bucket the traffic uses, and
        # the decode program.
        for n in sorted(set(lengths)):
            client.result(client.submit(
                rng.integers(1, cfg.vocab_size, size=(n,)).tolist(), 2,
            ), timeout=600)
        before = compile_event_count()
        rids = [client.submit(p, new) for p in prompts]
        served = [client.result(r, timeout=600) for r in rids]
        recompiles = compile_event_count() - before
        snap = engine.snapshot()
    finally:
        client.close()
        engine.stop()
    check(all(len(s) == new for s in served),
          f"incomplete requests: {[len(s) for s in served]}")

    # Reference 1: generate() on the same prompts (two per length, so
    # one compile per length).
    ref = []
    for i in range(0, len(prompts), 2):
        out = generate(module, params,
                       jnp.asarray(prompts[i:i + 2], jnp.int32), new)
        ref += [row[len(prompts[i]):].tolist() for row in np.asarray(out)]
    exact = sum(s == r for s, r in zip(served, ref))
    # Reference 2, for tokens that differ: the static forward's logits
    # along the engine's own sequence.  A served token is right when it
    # is the argmax, or within LOGIT_TIE_TOL of it (bf16 compute cannot
    # order two logits closer than that; after one such tie the two
    # greedy roll-outs legitimately part ways).
    width = max(lengths) + new
    seqs = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seqs[i, :len(p) + new] = p + s
    logits = np.asarray(jax.jit(module.forward)(params, jnp.asarray(seqs)))
    def gap(i, j, tok):  # how far below the argmax, predicting token j
        row = logits[i, len(prompts[i]) + j - 1]
        return float(row.max() - row[tok])

    gaps = [gap(i, j, tok) for i, s in enumerate(served)
            for j, tok in enumerate(s)]
    # Where a roll-out parts from generate()'s, both tokens must sit in
    # one near-tie of the logits their common prefix gives.
    split_gaps = [
        max(gap(i, j, s[j]), gap(i, j, r[j]))
        for i, (s, r) in enumerate(zip(served, ref))
        for j in range(new) if s[:j] == r[:j] and s[j] != r[j]
    ]
    emit({"phase": "serve", "wall_s": round(time.time() - t0, 1),
          "requests": len(prompts), "prompt_lengths": lengths,
          "new_tokens": new,
          "completed": int(snap["counters"]["completed"]),
          "equal_to_generate": exact,
          "near_tie_tokens": sum(g > 0 for g in gaps),
          "worst_logit_gap": max(gaps + split_gaps),
          "logit_tie_tol": LOGIT_TIE_TOL,
          "steady_state_recompiles": recompiles})
    check(all(len(s) == new for s in served),
          f"incomplete requests: {[len(s) for s in served]}")
    check(recompiles == 0, f"{recompiles} steady-state recompiles")
    check(max(gaps + split_gaps) <= LOGIT_TIE_TOL,
          f"a token is {max(gaps + split_gaps):.4f} below the reference "
          "argmax: not a bf16 near-tie")
    check(exact + len(split_gaps) == len(prompts),
          f"{len(prompts) - exact} requests differ from generate(), "
          f"{len(split_gaps)} explained by a near-tie")


def _rel(a, b, floor: float = 0.0) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.abs(a - b).max() / max(float(jnp.abs(b).max()), floor))


def kernel_phase(cfg, args) -> None:
    """Each Pallas kernel of the fit against its XLA path, forward and
    backward, at the fit's shapes, on this device.  The loss is a fixed
    random projection of the output — a well-conditioned functional
    (``sum(out**2)`` through a LayerNorm has a near-zero true gradient,
    and through attention makes ``delta = rowsum(dO*O)`` all
    cancellation: it measures bf16 rounding, not the kernel).  Both are
    also compared with the XLA path in f32 at the highest matmul
    precision, for the record."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.attention import xla_causal_attention
    from ray_lightning_tpu.ops.cross_entropy import (
        fused_lm_head_cross_entropy,
    )
    from ray_lightning_tpu.ops.flash_attention import flash_attention
    from ray_lightning_tpu.ops.layer_norm import layer_norm

    t0 = time.time()
    B, T, d, V = args.batch, cfg.seq_len, cfg.d_model, cfg.vocab_size
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 10)
    bf, f32 = jnp.bfloat16, jnp.float32
    qkv_shape = (B, T, cfg.n_head, cfg.head_dim)
    q, k, v = (jax.random.normal(kk, qkv_shape, bf) for kk in ks[:3])
    x = jax.random.normal(ks[3], (B, T, d), bf)
    g = 1.0 + 0.1 * jax.random.normal(ks[4], (d,), f32)
    b = 0.1 * jax.random.normal(ks[5], (d,), f32)
    wte = 0.02 * jax.random.normal(ks[6], (V, d), f32)
    tgt = jax.random.randint(ks[7], (B, T), 0, V)
    w_att = jax.random.normal(ks[8], qkv_shape, f32)
    w_ln = jax.random.normal(ks[9], (B, T, d), f32)

    def proj(fn, w):  # -> (scalar loss, the output itself)
        def loss(*a):
            o = fn(*a)
            return (o.astype(f32) * w).sum(), o
        return loss

    def ce(pallas, dtype=bf):
        def loss(x, w):
            o = fused_lm_head_cross_entropy(
                x, w, tgt, compute_dtype=dtype, use_pallas=pallas).mean()
            return o, o
        return loss

    def ln(pallas):
        return lambda x, g, b: layer_norm(x, g, b, use_pallas=pallas)

    # name -> (kernel path, XLA path, XLA path for f32 operands, operands)
    cases = {
        "flash": (proj(flash_attention, w_att),
                  proj(xla_causal_attention, w_att),
                  proj(xla_causal_attention, w_att), (q, k, v)),
        "layer_norm": (proj(ln(True), w_ln), proj(ln(False), w_ln),
                       proj(ln(False), w_ln), (x, g, b)),
        "cross_entropy": (ce(True), ce(False), ce(False, f32), (x, wte)),
    }
    out = {}
    for name, (kern, ref, ref32, operands) in cases.items():
        argnums = tuple(range(len(operands)))
        def vg(fn):
            return jax.jit(jax.value_and_grad(fn, argnums, has_aux=True))

        compiled = vg(kern).lower(*operands).compile()
        if jax.default_backend() == "tpu":
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: no Mosaic kernel in the compiled program")
        (_, ko), kg = compiled(*operands)
        (_, ro), rg = vg(ref)(*operands)
        with jax.default_matmul_precision("highest"):
            _, tg = vg(ref32)(*(a.astype(f32) for a in operands))
        floor = 1.0 if name == "flash" else 0.0
        errs = {"output": _rel(ko, ro, floor)}
        errs.update({f"grad{i}": _rel(a, r, floor)
                     for i, (a, r) in enumerate(zip(kg, rg))})
        if name == "cross_entropy":
            errs["loss_abs"] = abs(float(ko) - float(ro))
        out[name] = {
            "vs_xla": {k: float(f"{e:.3g}") for k, e in errs.items()},
            "grads_vs_f32": {
                "kernel": [float(f"{_rel(a, t, floor):.3g}")
                           for a, t in zip(kg, tg)],
                "xla": [float(f"{_rel(r, t, floor):.3g}")
                        for r, t in zip(rg, tg)],
            },
        }
    emit({"phase": "kernels", "wall_s": round(time.time() - t0, 1),
          "shapes": {"batch": B, "seq": T, "d_model": d, "vocab": V},
          "max_rel_err": out, "tol": KERNEL_TOL})
    for name, res in out.items():
        errs = res["vs_xla"]
        check(all(e == e and e <= KERNEL_TOL[name]
                  for k, e in errs.items() if k != "loss_abs"),
              f"{name} kernel off its XLA reference: {errs}")
    check(out["cross_entropy"]["vs_xla"]["loss_abs"] <= CE_LOSS_ATOL,
          f"CE loss off: {out['cross_entropy']['vs_xla']}")


# ---------------------------------------------------------------------------
# Four chips: data=4 and ZeRO-3 fsdp=4 against one device
# ---------------------------------------------------------------------------

def run_four_chips(args, expect: str) -> dict:
    cfg = model_config(args.size)
    on_tpu = expect == "tpu"
    steps = 3
    arms = {
        "data4": (RayStrategy(num_workers=1, mesh_axes={"data": 4}),
                  {"attention": "flash-island",
                   "cross_entropy": "pallas-island", "layer_norm": "xla"}),
        "zero3_fsdp4": (RayShardedStrategy(num_workers=1, zero_stage=3,
                                           mesh_axes={"fsdp": 4}),
                        {"attention": "flash-island",
                         "cross_entropy": "scan", "layer_norm": "xla"}),
    }
    probes = {}
    for name, (strategy, tpu_paths) in arms.items():
        p = probes[name] = fit(strategy, cfg, args, steps, expect, name)
        r = p.report
        check(r["device"]["count"] == 4, f"{name}: {r['device']}")
        check(r["pid"] != os.getpid() and not _alive(r["pid"]),
              f"{name}: worker {r['pid']} in-process or still alive")
        expect_kernels(p, tpu_paths if on_tpu else XLA_PATHS, name)
        step = r["programs"]["train/step"]
        check(step["collectives"]["all-reduce"]
              + step["collectives"]["reduce-scatter"] > 0,
              f"{name}: no gradient reduction in the step: {step}")
        for part, s in r["shards"].items():
            check(s["min_devices_per_leaf"] == 4,
                  f"{name}: a {part} leaf is missing from a device: {s}")
            share = max(s["bytes_per_device"]) / s["bytes"]
            sharded = name == "zero3_fsdp4" or (
                part == "opt_state" and r["update_sharding"] == "on")
            # Leaves under 4096 elements stay replicated, hence "about".
            check(share <= 0.3 if sharded else share == 1.0,
                  f"{name}: each device holds {share:.2f} of {part}")
        if name == "zero3_fsdp4":
            check(step["collectives"]["all-gather"] > 0,
                  f"{name}: ZeRO-3 step gathers no parameters: {step}")
        check(r["update_sharding"] == (
            "on" if on_tpu and name == "data4" else "off"),
            f"{name}: update_sharding={r['update_sharding']}")

    # The one-device reference, in this process on devices[:1], after
    # both workers have released the chips.
    import jax

    from ray_lightning_tpu.core.loop import run_fit
    from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh

    ref = Probe(expect, args.batch)
    trainer = Trainer(max_epochs=1, max_steps=steps, precision="bf16",
                      seed=args.seed, enable_checkpointing=False,
                      default_root_dir=f"{ROOT_DIR}/one_device")
    run_fit(
        GPT(cfg, attn_impl="auto", remat=True),
        SyntheticLMDataModule(cfg, batch_size=args.batch,
                              num_batches=steps + 1, seed=args.seed),
        trainer.config, [ref],
        mesh=build_mesh(MeshSpec({"data": 1}), devices=jax.devices()[:1]),
    )
    base = ref.loss_values
    emit({"phase": "fit/one_device", "losses": ref.losses, **ref.report})
    check(len(base) == steps, f"reference ran {len(base)} steps")
    for name, p in probes.items():
        check(close(p.loss_values, base),
              f"{name} losses {p.loss_values} differ from one device's "
              f"{base}")
    emit({"phase": "fit/agreement", "rtol": LOSS_RTOL, "one_device": base,
          **{n: p.loss_values for n, p in probes.items()}})
    device = dict(ref.report["device"])
    check(device["count"] == 4, f"this process sees {device}")
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--size", choices=("full", "tiny"), default="full",
                    help="tiny: a rehearsal's model (needs --rehearsal)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on the CPU backend on purpose (set "
                    "JAX_PLATFORMS=cpu); the result names the CPU")
    args = ap.parse_args()
    if args.size != "full" and not args.rehearsal:
        ap.error("--size tiny is a rehearsal's size: pass --rehearsal")
    expect = "cpu" if args.rehearsal else "tpu"

    def on_alarm(signum, frame):
        raise SmokeFailure(f"not done after {TIME_LIMIT_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TIME_LIMIT_S)
    t0 = time.time()
    try:
        run = run_one_chip if args.chips == 1 else run_four_chips
        device = run(args, expect)
        import jax

        here = jax.devices()
        check(here[0].platform == expect and len(here) == args.chips,
              f"asked for {args.chips} {expect} chip(s), JAX has {here}")
        check(device == {"platform": here[0].platform,
                         "kind": here[0].device_kind, "count": len(here)},
              f"phases ran on {device}, this process sees {here}")
    except SmokeFailure as e:
        # No result line: the failure goes to stderr, the code is 1.
        print(f"chip_smoke FAILED after {time.time() - t0:.0f} s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    emit({"phase": "done", "wall_s": round(time.time() - t0, 1)})
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
