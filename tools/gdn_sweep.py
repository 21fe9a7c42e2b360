"""Kernel-alone timing of the gated delta rule's decode on the chip.

``env PYTHONPATH=. python tools/gdn_sweep.py`` (on one TPU chip)
times one tick of the ``gigachat3.5-432b-a28b-ep16.serve-longdecode``
cell's linear layers (4 layers, 128 slots, 64 value heads of 128 x 128
float32 state over 32 key heads) two ways: ``rlt_gdn_decode``
(``ops/gated_delta.py``, the pool updated in place) and the XLA step of
the same arithmetic (``impl="xla"``), the pool donated to both.  Three
regimes: every slot live, half of them idle (every other slot), one
slot live.  Each regime first checks the kernel against the XLA step
(outputs and the whole pool after the tick, idle slots included); a time
is the tick's device time (median of 5 traced ticks), in ms, the
kernel's alone (``ms``) and the whole program's (``module_ms``).  Writes
``chiprun_out/gdn_sweep.jsonl``.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import xplane
from ray_lightning_tpu.ops import gated_delta as gd

OUT = "chiprun_out"
L, W, H, HK, D = 4, 128, 64, 32, 128


def operands(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        q=gd.l2norm(jax.random.normal(ks[0], (L, W, HK, D))) * D ** -0.5,
        k=gd.l2norm(jax.random.normal(ks[1], (L, W, HK, D))),
        v=jax.random.normal(ks[2], (L, W, H, D)),
        g=-jax.nn.softplus(jax.random.normal(ks[3], (L, W, H))),
        beta=jax.nn.sigmoid(jax.random.normal(ks[4], (L, W, H))))


def tick(impl):
    """Every linear layer's step of one tick, the pool donated."""
    def f(pool, x, lens):
        outs = []
        for i in range(L):
            o, pool = gd.decode_step(pool, jnp.int32(i), x["q"][i],
                                     x["k"][i], x["v"][i], x["g"][i],
                                     x["beta"][i], lens, impl=impl)
            outs.append(o)
        return jnp.stack(outs), pool
    return jax.jit(f, donate_argnums=0)


def fresh_pool(seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (L, W, H, D, D)) * 0.1


def device_ms(fn, x, lens, calls=5):
    """``(kernel ms, program ms)`` a tick: medians of ``calls`` traced
    ticks."""
    pool = fresh_pool()
    _, pool = fn(pool, x, lens)
    jax.block_until_ready(pool)
    tmp = tempfile.mkdtemp(prefix="gdn")
    kern, whole = [], []
    try:
        for _ in range(calls):
            jax.profiler.start_trace(tmp)
            _, pool = fn(pool, x, lens)
            jax.block_until_ready(pool)
            jax.profiler.stop_trace()
            trace = xplane.load(xplane.find_xplane(tmp))
            kern.append(sum(trace.op_runs("rlt_gdn_decode")) * 1e3)
            whole.append(sum((b - a) / 1e9 for d in trace.devices
                             for _, a, b in d.modules) * 1e3)
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return statistics.median(kern), statistics.median(whole)


def emit(fh, **row):
    fh.write(json.dumps(row) + "\n")
    fh.flush()
    print(json.dumps(row), flush=True)


def main():
    os.makedirs(OUT, exist_ok=True)
    fh = open(f"{OUT}/gdn_sweep.jsonl", "a")
    emit(fh, tag="device", kind=jax.devices()[0].device_kind,
         platform=jax.devices()[0].platform)
    x = operands()
    regimes = {
        "all_live": np.full(W, 900, np.int32),
        "half_idle": np.where(np.arange(W) % 2, 900, 0).astype(np.int32),
        "one_live": np.where(np.arange(W) == 37, 900, 0).astype(np.int32),
    }
    paths = {"pallas": tick("pallas"), "xla": tick("xla")}
    for name, lens in regimes.items():
        lens = jnp.asarray(lens)
        got = paths["pallas"](fresh_pool(), x, lens)
        want = paths["xla"](fresh_pool(), x, lens)
        emit(fh, tag="numerics", regime=name,
             out_max_abs=float(jnp.abs(got[0] - want[0]).max()),
             pool_max_abs=float(jnp.abs(got[1] - want[1]).max()),
             idle_kept=bool(jnp.all(jnp.where(
                 (lens > 0)[None, :, None, None, None], True,
                 got[1] == fresh_pool()))))
        del got, want
        for path, fn in paths.items():
            ms, module_ms = device_ms(fn, x, lens)
            emit(fh, tag="time", regime=name, path=path,
                 live=int((lens > 0).sum()), ms=round(ms, 4),
                 module_ms=round(module_ms, 4))


if __name__ == "__main__":
    main()
