"""Kernel-alone sweep of the paged decode walk on the chip (PR 35).

``chiprun -- env PYTHONPATH=. python tools/paged_sweep.py [--parent DIR,...]``
times ``rlt_paged_decode`` and ``rlt_mla_decode`` over every layer of a
tick at four length regimes: the GPT-2 serve cell's (16 slots x 32
blocks of 32, rows of 1280, 36 layers; prompts 128-768 log-uniform plus
a draw of their 16-128 answers), short slots on the same table (33 and
100 positions), K-EXAONE's two global layers (32 slots x 128 blocks,
rows of 1024, 64 query heads on 8) and sarvam's eight latent layers (64
slots x 224 blocks, rows of 640, 64 heads).  Each regime at walk widths
128 / 256 / 512 / 1024 positions a step, with and without the tail's
trim (``TAIL_POSITIONS`` set past the width: the last chunk computed
whole, as before PR 35); with ``--parent`` each module as it stands
under DIR (a ``git archive`` of the parent, or a variant of the walk)
at its own widths, a row ``tree`` the DIR's name.  A time is the kernels'
summed device time a tick (median of 5 traced ticks), in ms.  Writes
``chiprun_out/pr35/sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import statistics
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import xplane
from ray_lightning_tpu.ops import paged_attention as pa
from ray_lightning_tpu.ops.attention import _NEG_INF

OUT = "chiprun_out/pr35"
NO_TRIM = 1 << 30


def _lens(rng, w, prompt, answer):
    """In-flight lengths of a closed loop: a log-uniform prompt and a
    uniform share of a uniform answer already generated."""
    p = np.exp(rng.uniform(np.log(prompt[0]), np.log(prompt[1]), w))
    a = rng.uniform(answer[0], answer[1], w)
    return (p + rng.uniform(0, 1, w) * a).astype(np.int32)


def regimes(seed):
    rng = np.random.default_rng(seed)
    gpt2 = dict(kind="paged", W=16, M=32, Bs=32, H=20, Hkv=20, Dh=64, L=36)
    return {
        "gpt2_cell": dict(gpt2, lens=_lens(rng, 16, (128, 768), (16, 128))),
        "gpt2_33": dict(gpt2, lens=np.full(16, 33, np.int32)),
        "gpt2_100": dict(gpt2, lens=np.full(16, 100, np.int32)),
        "exaone_global": dict(
            kind="paged", W=32, M=128, Bs=32, H=64, Hkv=8, Dh=128, L=2,
            lens=_lens(rng, 32, (64, 3072), (64, 384))),
        "sarvam_latent": dict(
            kind="mla", W=64, M=224, Bs=32, H=64, r=512, row=640, L=8,
            lens=_lens(rng, 64, (512, 6144), (256, 1024))),
    }


def operands(g, seed=0):
    W, M, Bs, L = g["W"], g["M"], g["Bs"], g["L"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    tables = (1 + jax.random.permutation(ks[0], W * M)).reshape(W, M)
    tables, lens = tables.astype(jnp.int32), jnp.asarray(g["lens"])
    bf = lambda k, s: jax.random.normal(k, s, jnp.float32).astype(  # noqa
        jnp.bfloat16)
    if g["kind"] == "mla":
        row = g["row"]
        pool = bf(ks[1], (L, W * M + 1, Bs, row)).at[..., 576:].set(0)
        q = bf(ks[2], (W, g["H"], row)).at[..., 576:].set(0)
        return dict(q=q, cur=bf(ks[3], (W, row)), pool=pool,
                    tables=tables, lens=lens)
    hd = g["Hkv"] * g["Dh"]
    return dict(q=bf(ks[1], (W, g["H"] * g["Dh"])), kc=bf(ks[2], (W, hd)),
                vc=bf(ks[3], (W, hd)), pk=bf(ks[4], (L, W * M + 1, Bs, hd)),
                pv=bf(ks[5], (L, W * M + 1, Bs, hd)), tables=tables,
                lens=lens)


def tick(mod, g):
    """Every layer's kernel call of one tick, summed so none is dead."""
    if g["kind"] == "mla":
        def one(x, i):
            return mod.mla_decode_attention(
                x["q"], x["cur"], x["pool"], i, x["tables"], x["lens"],
                rank=g["r"], scale=0.135)
    else:
        def one(x, i):
            return mod.paged_decode_attention(
                x["q"], x["kc"], x["vc"], x["pk"], x["pv"], i, x["tables"],
                x["lens"], n_head=g["H"], n_kv_head=g["Hkv"],
                scale=g["Dh"] ** -0.5)

    def f(x):
        first = one(x, jnp.int32(0))
        return jax.lax.fori_loop(
            1, g["L"], lambda i, acc: acc + one(x, i), first)
    return jax.jit(f)


def device_ms(fn, x, calls=5):
    jax.block_until_ready(fn(x))
    tmp = tempfile.mkdtemp(prefix="sweep")
    try:
        per = []
        for _ in range(calls):
            jax.profiler.start_trace(tmp)
            jax.block_until_ready(fn(x))
            jax.profiler.stop_trace()
            runs = xplane.load(xplane.find_xplane(tmp)).op_runs(
                "rlt_paged_decode|rlt_mla_decode")
            per.append(sum(runs) * 1e3)
            shutil.rmtree(tmp, ignore_errors=True)
            os.makedirs(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return statistics.median(per)


def reference(g, x):
    """Layer 0 of the tick by the XLA path's arithmetic (float32)."""
    if g["kind"] == "mla":
        return pa.mla_decode_attention(
            x["q"], x["cur"], x["pool"], jnp.int32(0), x["tables"],
            x["lens"], rank=g["r"], scale=0.135, impl="xla")
    W, M, Bs, H, Hkv, Dh = (g[k] for k in ("W", "M", "Bs", "H", "Hkv", "Dh"))
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def ctx(pool, cur):
        rows = pool[0][x["tables"]].reshape(W, M * Bs, Hkv, Dh)
        return jnp.concatenate([f32(rows), f32(cur)[:, None].reshape(
            W, 1, Hkv, Dh)], 1)

    k, v = ctx(x["pk"], x["kc"]), ctx(x["pv"], x["vc"])
    q = f32(x["q"]).reshape(W, Hkv, H // Hkv, Dh)
    s = jnp.einsum("wgrd,wsgd->wgrs", q, k,
                   precision=jax.lax.Precision.HIGHEST) * Dh ** -0.5
    vis = jnp.concatenate([jnp.arange(M * Bs)[None] < x["lens"][:, None],
                           jnp.ones((W, 1), bool)], 1)
    p = jax.nn.softmax(jnp.where(vis[:, None, None], s, _NEG_INF), -1)
    return jnp.einsum("wgrs,wsgd->wgrd", p, v,
                      precision=jax.lax.Precision.HIGHEST).reshape(W, -1)


def emit(fh, **row):
    fh.write(json.dumps(row) + "\n")
    fh.flush()
    print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--seed", type=int, default=35)
    ap.add_argument("--only", default=None, help="comma-separated regimes")
    ap.add_argument("--widths", default="128,256,512,1024")
    ap.add_argument("--untrimmed", type=int, default=1,
                    help="also each width with its last chunk whole")
    a = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)
    fh = open(f"{OUT}/sweep.jsonl", "a")
    emit(fh, tag="device", kind=jax.devices()[0].device_kind,
         platform=jax.devices()[0].platform)
    parents = {}
    for i, tree in enumerate(a.parent.split(",") if a.parent else ()):
        spec = importlib.util.spec_from_file_location(
            f"parent_paged{i}", os.path.join(
                tree, "ray_lightning_tpu/ops/paged_attention.py"))
        parents[os.path.basename(tree.rstrip("/"))] = mod = (
            importlib.util.module_from_spec(spec))
        spec.loader.exec_module(mod)
    wide0, tail0 = pa.CHUNK_POSITIONS, pa.TAIL_POSITIONS
    for name, g in regimes(a.seed).items():
        if a.only and name not in a.only.split(","):
            continue
        x = operands(g)
        emit(fh, tag="regime", regime=name, mean_len=float(g["lens"].mean()),
             lens=[int(n) for n in g["lens"]])
        want = reference(g, x)
        got = tick(pa, dict(g, L=1))(x)
        emit(fh, tag="numerics", regime=name,
             max_abs=float(jnp.abs(got.reshape(want.shape) - want).max()))
        for tree, mod in parents.items():
            emit(fh, tag="parent", regime=name, tree=tree,
                 ms=round(device_ms(tick(mod, g), x), 4))
        for wide in map(int, a.widths.split(",")):
            for trim in (True, False) if a.untrimmed else (True,):
                if not trim and wide == 128:
                    continue        # the trim is the whole chunk there
                pa.CHUNK_POSITIONS = wide
                pa.TAIL_POSITIONS = tail0 if trim else NO_TRIM
                steps, pos = pa.walk_plan(g["lens"], g["M"], g["Bs"])
                try:
                    ms = device_ms(tick(pa, g), x)
                    emit(fh, tag="new", regime=name, wide=wide, trim=trim,
                         ms=round(ms, 4), steps=steps * g["L"],
                         positions=pos * g["L"])
                except Exception as e:  # noqa: BLE001 - a refusal is a row
                    emit(fh, tag="new", regime=name, wide=wide, trim=trim,
                         error=str(e).replace("\n", " ")[:300])
                finally:
                    pa.CHUNK_POSITIONS, pa.TAIL_POSITIONS = wide0, tail0
        del x


if __name__ == "__main__":
    main()
