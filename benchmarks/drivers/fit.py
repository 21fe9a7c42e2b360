"""Driver ``fit``: one in-process ``Trainer.fit`` on the chip, timed
between two host copies of the loss.

The fit is the user's: ``Trainer(strategy=LocalStrategy(), ...)`` on a
``GPT`` module and a data module, every setting the default a TPU user
gets except those the cell's ``system`` block names.  Three things are
the benchmark's:

* the data module streams the seeded pool of batches for as long as the
  window is open and ends the epoch at the next whole stride after it
  closes — a fit cannot be stopped by time from outside
  (``should_stop`` is honoured only at an epoch boundary), and an
  iterable that ends is the one way an epoch ends early without a
  change to the program;
* a callback (``on_train_batch_end`` fires once per megastep stride)
  opens the window after the warm-up strides by copying the loss to the
  host, reads only the host clock at every later stride end, and closes
  it at the first stride end past ``--seconds`` by copying the loss
  again.  Nothing else syncs inside the window that a user's fit would
  not;
* before the fit, loss and two gradient leaves of the module's own
  ``training_step`` are compared with ``reference/gpt2_ref.py`` at the
  full widths on two seeded sequences.
"""

from __future__ import annotations

import gc
import math
import os
import statistics
import threading
import time
from typing import Any, Dict, List

from benchmarks import flops as bench_flops
from benchmarks.lib import harness, traffic as traffic_lib
from benchmarks.reference import gpt2_ref

# Agreement with the float32 reference (system: bf16 compute, Pallas
# flash / LayerNorm / cross-entropy kernels, remat), measured on the
# chip at the full widths over nine seeds (my chip runs, PR 23):
#  * loss: at most 4.0e-5 relative over 15 runs (bf16 rounding of the
#    logits averages out over 2048 positions).  Bound: five times that.
#  * gradients, max|g - g_ref| / max|g_ref| over a whole stacked leaf,
#    through 24 bf16 layers: qkv_w 1.20e-2 to 1.57e-2, wte 1.12e-2 to
#    1.87e-2.  Bound: a little over twice the worst; a program that
#    computed in 8-bit floats (3 bits of mantissa against bf16's 8)
#    would miss it several times over.
LOSS_RTOL = 2e-4
GRAD_RTOL = 4e-2
GRAD_LEAVES = (("blocks", "qkv_w"), ("wte",))


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def reference_check(run: harness.Run, module, cfg) -> Dict[str, Any]:
    """System loss and gradients against the plain reference, on the
    device, before the fit holds its state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.time()
    seed = int(run.seed)
    rng = np.random.default_rng(seed + 1)
    tokens = jnp.asarray(rng.integers(
        0, cfg.vocab_size, size=(2, cfg.seq_len + 1)), jnp.int32)
    params = jax.jit(module.init_params)(jax.random.PRNGKey(seed % 2**31))
    step_rng = jax.random.PRNGKey(0)

    def pick(loss, grads):
        return (loss,) + tuple(_leaf(grads, p) for p in GRAD_LEAVES)

    @jax.jit
    def system(p, t):
        return pick(*jax.value_and_grad(
            lambda q: module.training_step(q, {"tokens": t}, step_rng)[0]
        )(p))

    @jax.jit
    def reference(p, t):
        return pick(*jax.value_and_grad(
            lambda q: gpt2_ref.loss(
                gpt2_ref.from_stacked(q, cfg.n_layer), t, cfg.n_head)
        )(p))

    got = system(params, tokens)
    # One sequence at a time: the float32 backward keeps every layer's
    # (heads, T, T) attention weights, 6.5 GB a sequence at these sizes.
    per_seq = [reference(params, tokens[i:i + 1])
               for i in range(tokens.shape[0])]
    want = tuple(sum(parts) / len(per_seq) for parts in zip(*per_seq))
    del per_seq
    loss_err = abs(float(got[0]) - float(want[0])) / abs(float(want[0]))
    grad_err = {
        "/".join(p): harness.rel_max_err(g, w)
        for p, g, w in zip(GRAD_LEAVES, got[1:], want[1:])
    }
    ok = (math.isfinite(loss_err) and loss_err <= LOSS_RTOL
          and all(math.isfinite(e) and e <= GRAD_RTOL
                  for e in grad_err.values()))
    out = {"ok": ok, "loss_system": float(got[0]),
           "loss_reference": float(want[0]), "loss_rel_err": loss_err,
           "grad_rel_err": grad_err, "loss_rtol": LOSS_RTOL,
           "grad_rtol": GRAD_RTOL, "seconds": time.time() - t0}
    del got, want, params, tokens, system, reference
    gc.collect()
    return out


class _UntilStopped:
    """The seeded pool of batches, over and over, until ``stop`` is
    set; ends only at a whole stride so that no ragged tail makes the
    trainer build its per-step program."""

    def __init__(self, loader, stride: int, stop: threading.Event):
        self.loader, self.stride, self.stop = loader, stride, stop

    def __iter__(self):
        n = 0
        while True:
            for batch in self.loader:
                if self.stop.is_set() and n % self.stride == 0:
                    return
                yield batch
                n += 1


def _make_datamodule(cfg, params: Dict[str, Any], seed: int,
                     stop: threading.Event):
    from ray_lightning_tpu.models import SyntheticLMDataModule

    class StreamingLM(SyntheticLMDataModule):
        """``SyntheticLMDataModule`` (the program's loader path) over
        the benchmark's own seeded pool, streamed until stopped."""

        def setup(self, stage: str) -> None:
            if self._tokens is None:
                self._tokens = traffic_lib.lm_token_pool(
                    params, seed, cfg.vocab_size)

        def train_dataloader(self):
            return _UntilStopped(
                super().train_dataloader(), int(params["stride"]), stop)

    return StreamingLM(cfg, batch_size=int(params["batch_size"]),
                       num_batches=int(params["pool_batches"]), seed=seed)


def _make_window(run: harness.Run, stop: threading.Event, warmup: int,
                 session, trace_s: float):
    import jax

    from ray_lightning_tpu.core.callbacks import Callback
    from ray_lightning_tpu.telemetry.step_stats import (
        compile_event_count, compile_time_total_s,
    )

    class Window(Callback):
        def __init__(self):
            self.strides = 0
            self.state = "warmup"
            self.marks: Dict[str, Dict[str, Any]] = {}
            self.losses: List[Any] = []      # (micro_step, device scalar)
            self.stride_ends: List[float] = []

        def setup(self, trainer, module, stage):
            compile_event_count()            # arm the listener

        def _mark(self, name, trainer, loss_value):
            tel = trainer.telemetry
            stats = getattr(tel, "step_stats", None)
            wait = getattr(stats, "_data_wait", None)
            self.marks[name] = {
                "clock": time.perf_counter(), "wall": time.time(),
                "micro_step": int(trainer.micro_step),
                "global_step": int(trainer.global_step),
                "loss": loss_value,
                "compile_events": compile_event_count(),
                "compile_s": compile_time_total_s(),
                "counters": dict(getattr(tel, "counters", {}) or {}),
                "data_wait_s": getattr(wait, "total", None),
                "meta": dict(getattr(tel, "meta", {}) or {}),
            }

        def on_train_batch_end(self, trainer, module, logs, batch_idx):
            self.strides += 1
            loss = logs["train_loss"]
            if self.state == "warmup":
                if self.strides >= warmup:
                    # The host copy of the loss waits for the device:
                    # the window opens on finished work.
                    self._mark("open", trainer, float(jax.device_get(loss)))
                    self.state = "open"
                return
            if self.state != "open":
                return
            now = time.perf_counter()
            self.stride_ends.append(now)
            self.losses.append((int(trainer.micro_step), loss))
            since = now - self.marks["open"]["clock"]
            if session is not None and not session.stopped:
                if not session.started:
                    if since >= min(2.0, run.seconds / 4):
                        session.start()
                elif time.time() - session.t_started >= trace_s:
                    session.stop()
                return
            if since >= run.seconds:
                self._mark("close", trainer, float(jax.device_get(loss)))
                self.state = "closed"
                stop.set()

    return Window()


def run(run: harness.Run) -> Dict[str, Any]:
    import jax

    from ray_lightning_tpu import LocalStrategy, Trainer
    from ray_lightning_tpu.models import GPT, GPTConfig

    device = harness.claim_device(run)
    cfg = GPTConfig(**run.config_fields())
    params, system = run.traffic(), run.system()
    if params["seq_len"] != cfg.seq_len:
        raise harness.BenchFailure("traffic seq_len != config seq_len")
    module = GPT(cfg, attn_impl=system.get("attn_impl", "auto"),
                 remat=bool(system.get("remat", True)))
    module.precision = system.get("precision", "bf16")

    check = reference_check(run, module, cfg)
    run.note(phase="reference_check", **check)
    run.mark("reference_checked")

    stop = threading.Event()
    session = harness.TraceSession(run) if run.trace else None
    window = _make_window(run, stop, int(system.get("warmup_strides", 2)),
                          session, float(system.get("trace_seconds", 6)))
    data = _make_datamodule(cfg, params, run.seed, stop)
    trainer = Trainer(
        strategy=LocalStrategy(megastep=system.get("megastep")),
        max_epochs=1, precision=module.precision, seed=run.seed,
        callbacks=[window], limit_val_batches=0,
        enable_checkpointing=False, log_every_n_steps=10**9,
        default_root_dir=os.path.join(run.work_dir, "fit", run.cell["name"]),
    )
    t_fit = time.time()
    run.mark("fit_called")
    try:
        trainer.fit(module, data)
    finally:
        stop.set()
        if session is not None and session.started and not session.stopped:
            session.stop()
    t_done = time.time()
    if "close" not in window.marks:
        raise harness.BenchFailure("the fit ended before the window closed")
    o, c = window.marks["open"], window.marks["close"]

    # -- the window ---------------------------------------------------------
    k = int(c["meta"].get("megastep") or 1)
    if k != int(params["stride"]):
        raise harness.BenchFailure(
            f"megastep resolved to K={k}, the traffic file says stride "
            f"{params['stride']}: the tail would run per step")
    steps = c["global_step"] - o["global_step"]
    window_s = c["clock"] - o["clock"]
    tokens = steps * int(params["batch_size"]) * cfg.seq_len
    chips = int(run.cell["chips"])
    tokens_per_s = tokens / window_s / chips
    setup_s = o["wall"] - run.t_start
    losses = [(s, float(v)) for s, v in
              zip([m for m, _ in window.losses],
                  jax.device_get([v for _, v in window.losses]))]
    bad_strides = sum(not math.isfinite(v) for _, v in losses)
    compiles_in_window = c["compile_events"] - o["compile_events"]
    # The loss falls: the median stride-final loss of the window lies
    # below the loss at its opening.  Not "the last below the first":
    # on random tokens at the default learning rate single strides
    # spike (one seed of six closed at 11.009 after opening at
    # 11.006 with 10.68 three strides earlier; my chip runs, PR 23).
    loss_median = statistics.median(v for _, v in losses)
    loss_fell = loss_median < o["loss"]
    correct = (check["ok"] and compiles_in_window == 0
               and bad_strides == 0 and loss_fell
               and math.isfinite(o["loss"]) and math.isfinite(c["loss"]))

    # -- earlier lines ------------------------------------------------------
    mem = harness.memory_report(device)
    fpt = bench_flops.model_flops_per_token(cfg)
    mfu = None
    if device["platform"] == "tpu":
        peak = bench_flops.peaks_for(device["kind"])["bf16_flops_per_s"]
        mfu = tokens_per_s * fpt / peak
    run.note(phase="fit", window_s=window_s, steps=steps, tokens=tokens,
             strides_in_window=len(window.stride_ends),
             tokens_per_s_per_chip=tokens_per_s, model_flops_per_token=fpt,
             mfu_no_recompute_credit=mfu, loss_open=o["loss"],
             loss_close=c["loss"], loss_median=loss_median,
             stride_losses=losses[:3] + losses[-3:],
             compile_events_in_window=compiles_in_window,
             compile_events_setup=o["compile_events"],
             compile_s_setup=o["compile_s"], setup_s=setup_s,
             fit_wall_s=time.time() - t_fit, megastep_k=k,
             timeline={**run.timeline,
                       "window_open": round(o["wall"] - run.t_start, 3),
                       "window_close": round(c["wall"] - run.t_start, 3),
                       "fit_returned": round(t_done - run.t_start, 3)},
             kernel_paths=module.kernel_paths(int(params["batch_size"])),
             cache_dir=jax.config.jax_compilation_cache_dir, memory=mem)

    dc = {key: c["counters"].get(key, 0) - o["counters"].get(key, 0)
          for key in set(c["counters"]) | set(o["counters"])
          if isinstance(c["counters"].get(key, 0), (int, float))}
    trace = session.load() if session is not None else None
    obs = {
        "trace": trace, "cfg": cfg, "device": device,
        "counters": {
            **dc, "optimizer_steps": steps, "megastep_k": k,
            "compile_s_setup": o["compile_s"],
            "data_wait_s": (None if c["data_wait_s"] is None
                            else c["data_wait_s"] - o["data_wait_s"]),
        },
        "shapes": {"batch": int(params["batch_size"]),
                   "seq": cfg.seq_len, "n_head": cfg.n_head,
                   "head_dim": cfg.head_dim, "n_layer": cfg.n_layer},
    }
    return harness.finish(
        run, correct=correct, attempted=steps,
        failed=bad_strides * k,
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        obs=obs, device=device, trace=trace)
