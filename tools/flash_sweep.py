"""Kernel-alone sweep of the flash kernels on the chip (PR 31).

``chiprun -- env PYTHONPATH=. python tools/flash_sweep.py [--parent DIR]``
times ``_flash_fwd_bhsd`` (with lse) and ``_flash_bwd_bhsd`` at the fit
cell's shapes over a grid of tile walks, and the primal forward at the two
serve cells' prefill shapes; with ``--parent`` the same for the module
as it stood before PR 31 (a ``git archive`` of such a commit under DIR:
``_flash_fwd_bhsd(q, k, v, scale, block_q, block_k)``).  Times are the
kernel's own device time (median of 8 traced calls) beside host wall over
``--calls`` back-to-back calls ending in a host copy, in microseconds a
call.  Writes ``chiprun_out/pr31/sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import statistics
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import xplane
from ray_lightning_tpu.ops import flash_attention as fa
from ray_lightning_tpu.ops.attention import xla_causal_attention

OUT = "chiprun_out/pr31"


def timed(fn, args, calls):
    """(device us a kernel call from a trace, host wall us a call)."""
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        np.asarray(jax.tree_util.tree_leaves(out)[0][0, 0, :1])
        best = min(best, (time.perf_counter() - t0) / calls)
    tmp = tempfile.mkdtemp(prefix="sweep")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(8):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        runs = xplane.load(xplane.find_xplane(tmp)).op_runs("rlt_flash")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    dev = statistics.median(runs) * 1e6 if runs else float("nan")
    return dev, best * 1e6


def emit(fh, **row):
    fh.write(json.dumps(row) + "\n")
    fh.flush()
    print(json.dumps(row), flush=True)


def walk_of(base, **w):
    """``base`` with some fields replaced, its step and strip kept legal."""
    walk = base._replace(**w)
    step = math.gcd(walk.block_q, walk.block_k)
    return walk._replace(step=step, sub=min(walk.sub, step))


def tensors(bh, s, d, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda r, w: jax.random.normal(r, (bh, s, w), jnp.float32).astype(
        jnp.bfloat16)
    return mk(ks[0], d), mk(ks[1], d), mk(ks[2], dv), mk(ks[3], dv)


def try_timed(fh, tag, fn, args, calls, **row):
    try:
        us, wall = timed(fn, args, calls)
        emit(fh, tag=tag, us=round(us, 1), wall_us=round(wall, 1), **row)
    except Exception as e:  # noqa: BLE001 - a refused shape is a row
        emit(fh, tag=tag, error=str(e).replace("\n", " ")[:200], **row)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--calls", type=int, default=40)
    a = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)
    fh = open(f"{OUT}/sweep.jsonl", "a")
    emit(fh, tag="device", kind=jax.devices()[0].device_kind,
         platform=jax.devices()[0].platform)
    bh, s, d = 128, 1024, 64
    scale = d ** -0.5
    q, k, v, do = tensors(bh, s, d, d)

    # -- numerics on the chip: the default walk against XLA ------------
    q4, k4, v4 = (x.reshape(8, 16, s, d).transpose(0, 2, 1, 3)
                  for x in (q, k, v))
    loss = lambda f: lambda q, k, v: (f(q, k, v).astype(jnp.float32) ** 2).sum()
    up = lambda x: x.astype(jnp.float32)
    ref_o = xla_causal_attention(up(q4), up(k4), up(v4))
    new_o = jax.jit(fa.flash_attention)(q4, k4, v4)
    ref_g = jax.jit(jax.grad(loss(xla_causal_attention), (0, 1, 2)))(
        up(q4), up(k4), up(v4))
    new_g = jax.jit(jax.grad(loss(fa.flash_attention), (0, 1, 2)))(q4, k4, v4)
    rel = lambda a, b: float(jnp.abs(up(a) - b).max() / jnp.abs(b).max())
    emit(fh, tag="numerics", out=rel(new_o, ref_o),
         grads=[rel(x, y) for x, y in zip(new_g, ref_g)])

    if a.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_flash", os.path.join(
                a.parent, "ray_lightning_tpu/ops/flash_attention.py"))
        pf = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pf)
        par_g = jax.jit(jax.grad(loss(pf.flash_attention), (0, 1, 2)))(
            q4, k4, v4)
        emit(fh, tag="numerics_parent",
             out=rel(jax.jit(pf.flash_attention)(q4, k4, v4), ref_o),
             grads=[rel(x, y) for x, y in zip(par_g, ref_g)])
        out, lse = jax.jit(lambda q, k, v: pf._flash_fwd_bhsd(
            q, k, v, scale, 512, 512))(q, k, v)
        for bq, bk in ((512, 512), (256, 512), (256, 256)):
            try_timed(fh, "parent_fwd", lambda q, k, v: pf._flash_fwd_bhsd(
                q, k, v, scale, bq, bk), (q, k, v), a.calls, bq=bq, bk=bk)
            try_timed(fh, "parent_bwd", lambda *x: pf._flash_bwd_bhsd(
                *x, scale, bq, bk), (q, k, v, out, lse, do), a.calls,
                bq=bq, bk=bk)

    base = fa._pick_walk(s, d, 2, scale, None, None)
    emit(fh, tag="default_walk", **base._asdict())
    out, lse = jax.jit(lambda q, k, v: fa._flash_fwd_bhsd(
        q, k, v, scale, base))(q, k, v)
    fwd_grid = [dict(block_q=bq, block_k=bq, sub=sub)
                for bq, sub in ((512, 128), (512, 256), (512, 512),
                                (1024, 128), (1024, 256), (1024, 512),
                                (1024, 1024))]
    for w in fwd_grid:
        walk = walk_of(base, **w)
        try_timed(fh, "new_fwd", lambda q, k, v: fa._flash_fwd_bhsd(
            q, k, v, scale, walk), (q, k, v), a.calls, **w)
    bwd_grid = [dict(block_q=bq, block_k=bk, sub=sub, span=span)
                for bq, bk, sub, span in (
                    (512, 512, 128, 1024), (512, 512, 256, 1024),
                    (512, 512, 512, 1024), (1024, 1024, 128, 1024),
                    (1024, 1024, 256, 1024), (1024, 1024, 512, 1024),
                    (512, 1024, 128, 1024), (512, 1024, 256, 1024),
                    (256, 1024, 256, 1024))]
    for w in bwd_grid:
        walk = walk_of(base, **w)
        try_timed(fh, "new_bwd", lambda *x: fa._flash_bwd_bhsd(
            *x, scale, walk), (q, k, v, out, lse, do), a.calls, **w)

    # -- the serve cells' prefill shapes: primal forward ----------------
    for name, (hb, ss, dd, dvv, sc) in {
            "exaone_3072": (64, 3072, 128, 128, 128 ** -0.5),
            "exaone_1024": (64, 1024, 128, 128, 128 ** -0.5),
            "sarvam_6144": (64, 6144, 192, 128, 0.1),
            "sarvam_2048": (64, 2048, 192, 128, 0.1)}.items():
        qq, kk, vv, _ = tensors(hb, ss, dd, dvv, 1)
        if a.parent:
            try_timed(fh, "parent_primal", lambda q, k, v: pf._flash_fwd_bhsd(
                q, k, v, sc, 512, 512, want_lse=False)[0], (qq, kk, vv),
                10, shape=name)
        b0 = fa._pick_walk(ss, dd, 2, sc, None, None)
        for w in (dict(), dict(block_q=1024, block_k=512),
                  dict(block_q=512, block_k=512)):
            walk = walk_of(b0, **w)
            try_timed(fh, "new_primal", lambda q, k, v: fa._flash_fwd_bhsd(
                q, k, v, sc, walk, want_lse=False)[0], (qq, kk, vv), 10,
                shape=name, **walk._asdict())
        if name == "sarvam_2048":
            x4 = lambda x: x.reshape(1, hb, ss, -1).transpose(0, 2, 1, 3)
            r = xla_causal_attention(up(x4(qq)), up(x4(kk)), up(x4(vv)), sc)
            o = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, sc))(
                x4(qq), x4(kk), x4(vv))
            emit(fh, tag="numerics_sarvam_2048", out=rel(o, r))


if __name__ == "__main__":
    main()
