"""Compile the main path's kernels and step for a *described* TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host and the installed TPU compiler lowers for it, raising
what the real chip's compiler would raise (unaligned block shapes, VMEM
overflow, Mosaic kernels under the GSPMD partitioner).  Nothing runs, so
these say nothing about results or memory at run time — ``chip_smoke.py``
does that on the chip.  They guard, at no chip time, what the removed
run-time kernel probe used to paper over.

All of it lives in this one file: the worker that runs it loads libtpu
and holds its lock until exit.  The topology and everything built from
it sit in module-scoped fixtures — never at import, in ``skipif`` or in
``parametrize`` — so every xdist worker collects the same tests.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

# GPT-2-small training shapes (B=16, T=1024, d=768, H=12, V=50304).
B, T, D, H, DH, V = 16, 1024, 768, 12, 64, 50304
N_TOK = B * T  # 16384 tokens -> 32 LayerNorm/CE token blocks
# The serve cell (benchmarks/workloads/gpt2-large.serve-long.json):
# GPT-2-large, 16 slots x 32 table entries of 32-position blocks, the
# engine's default pool of 545 blocks.
SERVE = dict(n_layer=36, n_head=20, W=16, M=32, Bs=32, N=545)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("data",))


@pytest.fixture(scope="module", autouse=True)
def _as_tpu(topo):
    """Steer the program's own backend checks to their TPU branch (the
    process is pinned to the CPU; ``_interpret()`` and the model's
    ``on_tpu`` gates read ``jax.default_backend``), and keep the
    persistent compile cache off: an executable compiled for a described
    chip is written but can never be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    mp.undo()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# -- single-chip kernels ----------------------------------------------------

def _flash_args(sh):
    return tuple(_sds((B, T, H, DH), jnp.bfloat16, sh) for _ in range(3))


def _flash_loss(q, k, v):
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v).astype(jnp.float32).sum()


def _ce_args(sh, rep=None):
    rep = sh if rep is None else rep
    return (_sds((B, T, D), jnp.bfloat16, sh),
            _sds((V, D), jnp.float32, rep),
            _sds((B, T), jnp.int32, sh))


def _ce_loss(x, w, t):
    from ray_lightning_tpu.ops.cross_entropy import (
        fused_lm_head_cross_entropy,
    )

    return fused_lm_head_cross_entropy(
        x, w, t, compute_dtype=jnp.bfloat16, use_pallas=True
    ).mean()


def _ln_args(sh):
    return (_sds((B, T, D), jnp.bfloat16, sh),
            _sds((D,), jnp.float32, sh), _sds((D,), jnp.float32, sh))


def _ln_loss(x, g, b):
    from ray_lightning_tpu.ops.layer_norm import layer_norm

    return layer_norm(x, g, b, use_pallas=True).astype(jnp.float32).sum()


_KERNELS = {
    "flash": (_flash_loss, _flash_args, (0, 1, 2)),
    "ce": (_ce_loss, _ce_args, (0, 1)),
    "ln": (_ln_loss, _ln_args, (0, 1, 2)),
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("family", sorted(_KERNELS))
def test_kernel_compiles_at_gpt2_small_width(one_chip, family, grad):
    """flash / CE / LayerNorm, forward and forward+backward, at the
    fit's shapes.  16384 tokens give 32 token blocks: the LayerNorm
    backward's per-block partials were refused at exactly this."""
    loss, make_args, argnums = _KERNELS[family]
    fn = jax.grad(loss, argnums=argnums) if grad else loss
    _compile(fn, *make_args(one_chip))


def test_bgmv_pallas_compiles_for_eight_rows(one_chip):
    from ray_lightning_tpu.ops.lora import bgmv_pallas

    W, r, n_adapters = 8, 16, 4
    assert "rlt_lora_bgmv" in _compile(
        bgmv_pallas,
        _sds((W, D), jnp.bfloat16, one_chip),
        _sds((n_adapters, D, r), jnp.bfloat16, one_chip),
        _sds((n_adapters, r, 3 * D), jnp.bfloat16, one_chip),
        _sds((W,), jnp.int32, one_chip),
    )


@pytest.mark.parametrize("pool,q", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.float32, jnp.float32),
    (jnp.bfloat16, jnp.float32),
], ids=["bf16", "f32", "f32_query_bf16_pool"])
def test_paged_decode_kernel_compiles_at_serve_cell_shapes(one_chip, pool, q):
    """One layer's paged decode attention at ``gpt2-large.serve-long``'s
    shapes, in each mix of dtypes the kernel takes (a float32 operand
    against a bf16 pool goes through the three-term split)."""
    from ray_lightning_tpu.ops.paged_attention import paged_decode_attention

    hd = SERVE["n_head"] * DH
    row = _sds((SERVE["W"], hd), pool, one_chip)
    kv = _sds((SERVE["n_layer"], SERVE["N"], SERVE["Bs"], hd), pool, one_chip)
    assert "rlt_paged_decode" in _compile(
        lambda *a: paged_decode_attention(
            *a, n_head=SERVE["n_head"], scale=DH ** -0.5),
        _sds((SERVE["W"], hd), q, one_chip), row, row, kv, kv,
        _sds((), jnp.int32, one_chip),
        _sds((SERVE["W"], SERVE["M"]), jnp.int32, one_chip),
        _sds((SERVE["W"],), jnp.int32, one_chip),
    )


# -- kernels under a four-chip data mesh ------------------------------------

def test_flash_compiles_under_data4_mesh(data4):
    """``impl="auto"`` on a batch-only mesh: the kernel must sit inside a
    shard_map island — bare, the partitioner refuses it ("Mosaic kernels
    cannot be automatically partitioned")."""
    from ray_lightning_tpu.ops.attention import causal_attention

    sh = NamedSharding(data4, P("data"))

    def loss(q, k, v):
        out = causal_attention(q, k, v, impl="auto", mesh=data4)
        return out.astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_flash_args(sh))
    # Batch-local: attention needs no collective at all.
    assert "all-gather" not in text and "all-to-all" not in text


def test_sharded_ce_island_compiles_under_data4_mesh(data4):
    from ray_lightning_tpu.ops.cross_entropy import (
        fused_lm_head_cross_entropy_sharded,
    )

    def loss(x, w, t):
        return fused_lm_head_cross_entropy_sharded(
            x, w, t, data4, compute_dtype=jnp.bfloat16
        ).mean()

    text = _compile(
        jax.grad(loss, argnums=(0, 1)),
        *_ce_args(NamedSharding(data4, P("data")),
                  NamedSharding(data4, P())),
    )
    assert "all-reduce" in text  # the dwte psum


# -- the whole single-chip train step ---------------------------------------

def _train_step(one_chip, cfg, batch_size):
    """``Trainer.fit``'s single-device step program (bf16, remat) for a
    configuration and batch, compiled for the described chip."""
    from types import SimpleNamespace

    from ray_lightning_tpu.core.module import TrainState
    from ray_lightning_tpu.models import GPT
    from ray_lightning_tpu.parallel.step_fns import _single_device_raw_step

    module = GPT(cfg, attn_impl="auto", remat=True)
    module.precision = "bf16"
    module.trainer = SimpleNamespace(mesh=None, step_mode="gspmd")
    tx = module.configure_optimizers()
    if isinstance(tx, tuple) and not hasattr(tx, "init"):
        tx = tx[0]
    abstract = jax.eval_shape(
        lambda r: TrainState.create(module.init_params(r), tx),
        jax.random.PRNGKey(0),
    )
    state = jax.tree_util.tree_map(
        lambda l: _sds(l.shape, l.dtype, one_chip), abstract
    )
    batch = {"tokens": _sds((batch_size, cfg.seq_len + 1), jnp.int32,
                            one_chip)}
    rng = _sds((2,), jnp.uint32, one_chip)
    return jax.jit(
        _single_device_raw_step(module, tx), donate_argnums=0
    ).lower(state, batch, rng).compile()


@pytest.fixture(scope="module")
def small_step(one_chip):
    """GPT-2-small at B=16, compiled once for the file."""
    from ray_lightning_tpu.models import GPTConfig

    return _train_step(one_chip, GPTConfig.gpt2_small(), B)


def test_gpt2_small_train_step_compiles_and_fits(small_step):
    """Every kernel present, and arguments plus temporaries inside the
    chip's 16 GB."""
    text = small_step.as_text()
    # flash fwd + dq + dkdv, CE fwd + dx + dw, LN fwd + bwd (several
    # sites each, scanned): at least 8 distinct Mosaic calls.
    assert text.count("tpu_custom_call") >= 8
    mem = small_step.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"step needs {total / 1e9:.1f} GB"


@pytest.mark.parametrize("kernel", [
    "rlt_flash_fwd", "rlt_flash_bwd", "rlt_ce_fwd", "rlt_ce_bwd_dx",
    "rlt_ce_bwd_dw", "rlt_ln_fwd", "rlt_ln_bwd",
])
def test_step_program_names_its_kernels(small_step, kernel):
    """``pallas_call(name=...)`` reaches the compiled program: the
    Mosaic custom call is an instruction named after the kernel
    (wrapped in the transformation's name where one applies:
    ``jvp_rlt_ce_fwd_``), which is what a device trace's ``XLA Ops``
    event shows.  The device metrics match on it."""
    import re

    heads = [line.split(" custom-call(")[0].split(" = ")[0].strip()
             for line in small_step.as_text().splitlines()
             if "tpu_custom_call" in line]
    rx = re.compile(rf"^(ROOT )?%\w*{kernel}[_.\d]*$")
    assert any(rx.match(h) for h in heads), (kernel, sorted(set(heads)))


# -- the fit cell's step: what the benchmark's flash metrics read -----------

def _bench_file(rel):
    import json

    root = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks")
    with open(os.path.join(root, rel)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def fit_step(one_chip):
    """The ``gpt2-medium.fit`` cell's step program (its configuration and
    batch read from the benchmark's own files), compiled once for the
    file, and the shapes its flash calls have."""
    from ray_lightning_tpu.models import GPTConfig

    cfg = GPTConfig(**_bench_file("configs/gpt2-medium.json")["fields"])
    batch_size = _bench_file("traffic/fit.json")["batch_size"]
    return _train_step(one_chip, cfg, batch_size), dict(
        bh=batch_size * cfg.n_head, seq=cfg.seq_len,
        head_dim=cfg.d_model // cfg.n_head)


@pytest.mark.parametrize("metric,kernel", [
    ("flash_fwd_roofline.train", "rlt_flash_fwd"),
    ("flash_bwd_roofline.train", "rlt_flash_bwd"),
])
def test_fit_step_flash_calls_match_the_benchmarks_patterns(
        fit_step, metric, kernel):
    """``benchmarks/readers/attention_roofline.py`` finds the flash
    kernels by their RESULT TUPLE (the pattern in the metric's own file)
    and divides one call's cost by the mean time of the matches: the
    layers' scanned body must hold exactly one custom call that matches,
    and it must be the kernel the metric is named for."""
    import re

    compiled, shapes = fit_step
    pattern = _bench_file(f"layer_metrics/{metric}.json")["args"]["pattern"]
    rx = re.compile(pattern.format(**shapes))
    calls = [line.strip() for line in compiled.as_text().splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    hits = [c for c in calls if rx.search(c.removeprefix("ROOT "))]
    assert len(hits) == 1, (metric, [c[:160] for c in calls])
    assert kernel in hits[0].split(" = ")[0]
    named = [c for c in calls if kernel in c.split(" = ")[0]]
    assert named == hits, "a call of the kernel the pattern does not match"


def test_fit_step_fits_where_the_parent_did(fit_step):
    """The cell's step stood at 13.83 GB of 15.75 before PR 31; the walk
    may not cost it memory (the HBM lse stays 8 lanes wide)."""
    from ray_lightning_tpu.ops.flash_attention import _STAT_W

    assert _STAT_W == 8
    assert _footprint(fit_step[0]) <= 13.84e9


@pytest.mark.parametrize("heads,seq,width,value_width,scale", [
    (64, 3072, 128, 128, None),      # K-EXAONE's full-attention layers
    (64, 6144, 192, 128, 0.1),       # sarvam's: q/k 192, values 128
], ids=["k-exaone-3072", "sarvam-6144"])
def test_flash_forward_compiles_at_serve_prefill_shapes(
        one_chip, heads, seq, width, value_width, scale):
    """The primal forward at the two prefills' largest buckets: K and V of
    a head resident, inside VMEM."""
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    q = _sds((1, seq, heads, width), jnp.bfloat16, one_chip)
    v = _sds((1, seq, heads, value_width), jnp.bfloat16, one_chip)
    text = _compile(lambda q, k, v: flash_attention(q, k, v, scale), q, q, v)
    assert "%rlt_flash_fwd" in text


# -- the serve cell's decode program ----------------------------------------

@pytest.fixture(scope="module")
def serve_decode(one_chip):
    """``paged_decode_step`` as the engine jits it (pool donated) for
    GPT-2-large at the serve cell's shapes: the float32 params as the
    engine holds them for bf16 compute (``prepare_params``), bf16
    pool."""
    from ray_lightning_tpu.models import GPT, GPTConfig
    from ray_lightning_tpu.serve.kv_cache import (
        GPTServeFamily, PagedKVCache, paged_decode_step,
    )

    cfg = GPTConfig(n_layer=SERVE["n_layer"], n_head=SERVE["n_head"],
                    d_model=SERVE["n_head"] * DH)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda l: _sds(l.shape, l.dtype, one_chip), tree)

    params = abstract(jax.eval_shape(
        lambda key: GPTServeFamily(cfg).prepare_params(
            GPT(cfg, attn_impl="auto").init_params(key), jnp.bfloat16),
        jax.random.PRNGKey(0)))
    pool = abstract(jax.eval_shape(
        PagedKVCache(cfg, SERVE["N"], SERVE["Bs"], jnp.bfloat16).init_pool))

    def step(params, pool, tables, seq_lens, tokens):
        return paged_decode_step(cfg, params, pool, tables, seq_lens,
                                 tokens, compute_dtype=jnp.bfloat16)

    return jax.jit(step, donate_argnums=1).lower(
        params, pool, _sds((SERVE["W"], SERVE["M"]), jnp.int32, one_chip),
        _sds((SERVE["W"],), jnp.int32, one_chip),
        _sds((SERVE["W"],), jnp.int32, one_chip),
    ).compile()


def test_serve_decode_step_compiles_names_its_kernel_and_fits(serve_decode):
    """The kernel is in the program under its name, and arguments plus
    temporaries need less than the parent's whole-table gather did
    (12.90 GB, the same compile at PR 24's tree; the chip has 15.75)."""
    assert "%rlt_paged_decode" in serve_decode.as_text()
    mem = serve_decode.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 12.9e9, f"decode needs {total / 1e9:.2f} GB"


def test_serve_decode_step_converts_no_weight(serve_decode):
    """The engine's tree is in the dtype the program reads: the program
    holds 1.55 GB of bf16 weights and no float32 copy of them (with the
    caller's float32 tree it needed 7.73 GB: 3.1 GB of arguments and
    1.4 GB of converted temporaries more), and no ``convert`` makes a
    whole stack of a weight or a table."""
    import re

    total = _footprint(serve_decode)
    assert total < 5.2e9, f"decode needs {total / 1e9:.2f} GB"
    L, d = SERVE["n_layer"], SERVE["n_head"] * DH
    stacks = {L * d * 3 * d, L * d * d, L * d * 4 * d, V * d, 1024 * d}
    rx = re.compile(
        r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* convert\(", re.M)
    found = [m.group(0).strip()[:160]
             for m in rx.finditer(serve_decode.as_text())
             if int(np.prod([int(n) for n in m.group(1).split(",")]))
             in stacks]
    assert not found, found


@pytest.mark.parametrize("opcode", ["copy", "convert", "gather",
                                    "dynamic-slice"])
def test_serve_decode_step_moves_no_pool_layer(serve_decode, opcode):
    """No operation of the decode program copies, converts, gathers or
    slices out a pool layer, the whole pool, or every slot's whole table
    of positions: the whole-pool copy is found here, before chip time."""
    import re

    hd = SERVE["n_head"] * DH
    layer = SERVE["N"] * SERVE["Bs"] * hd
    whole = {layer, SERVE["n_layer"] * layer,
             SERVE["W"] * SERVE["M"] * SERVE["Bs"] * hd}
    rx = re.compile(
        r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w\-]+)\(", re.M)
    found = [m.group(0).strip()[:160]
             for m in rx.finditer(serve_decode.as_text())
             if m.group(2).startswith(opcode)
             and int(np.prod([int(d) for d in m.group(1).split(",")]))
             in whole]
    assert not found, found


# -- the expert-parallel share (k-exaone-236b-a23b-ep8.serve-mixed) ---------

# benchmarks/workloads/k-exaone-236b-a23b-ep8.serve-mixed.json: 32 slots x
# 4096 positions in blocks of 32, the engine's default pool of 33 x 128 + 1
# blocks, rings of 5 blocks; published widths, 16 of 128 experts held.
MOE = dict(W=32, M=128, Bs=32, N=33 * 128 + 1, d=6144, f=2048, E=16, k=8)


def _moe_cfg():
    from ray_lightning_tpu.models.exaone_moe import ExaoneMoEConfig

    return ExaoneMoEConfig(n_layer=8, experts_held=(0, 16),
                           vocab_held=(0, 19200), seq_len=4096)


@pytest.mark.parametrize("rows", [MOE["W"], 3072], ids=["decode", "prefill"])
def test_moe_kernels_compile_at_serve_cell_shapes(one_chip, rows, request):
    """The grouped matmuls at a decode tick's 32 x 8 assignments (tiles of
    16 rows, weight-streaming) and at the largest prefill bucket's 3072 x
    8 (tiles of 128), there in the prefill program they run in."""
    from ray_lightning_tpu.ops.moe import dropless_moe

    if rows == 3072:
        text = request.getfixturevalue("moe_prefill").as_text()
    else:
        w = _sds((MOE["E"], MOE["d"], MOE["f"]), jnp.bfloat16, one_chip)
        text = _compile(
            lambda x, idx, gates, wg, wu, wd: dropless_moe(
                x, idx, gates, wg, wu, wd, 0, impl="pallas"),
            _sds((rows, MOE["d"]), jnp.bfloat16, one_chip),
            _sds((rows, MOE["k"]), jnp.int32, one_chip),
            _sds((rows, MOE["k"]), jnp.float32, one_chip), w, w,
            _sds((MOE["E"], MOE["f"], MOE["d"]), jnp.bfloat16, one_chip))
    assert "%rlt_moe_gate_up" in text and "%rlt_moe_down" in text


def test_gqa_paged_decode_kernel_compiles_at_serve_cell_shapes(one_chip):
    """64 query heads on 8 K/V heads of 128 over the two full layers'
    pool."""
    from ray_lightning_tpu.ops.paged_attention import paged_decode_attention

    row = _sds((MOE["W"], 1024), jnp.bfloat16, one_chip)
    kv = _sds((2, MOE["N"], MOE["Bs"], 1024), jnp.bfloat16, one_chip)
    assert "rlt_paged_decode" in _compile(
        lambda *a: paged_decode_attention(
            *a, n_head=64, n_kv_head=8, scale=128 ** -0.5),
        _sds((MOE["W"], 8192), jnp.bfloat16, one_chip), row, row, kv, kv,
        _sds((), jnp.int32, one_chip),
        _sds((MOE["W"], MOE["M"]), jnp.int32, one_chip),
        _sds((MOE["W"],), jnp.int32, one_chip),
    )


def test_gqa_paged_decode_kernel_compiles_with_values_of_their_own_width(
        one_chip):
    """64 query heads on 4 K/V heads, keys 192 wide and values 128, over
    the three full layers' pool of ``mimo-v2-flash-ep16.serve-longprompt``
    (32 slots x 212 blocks of 32, the engine's default 33 x 212 + 1
    blocks; rows of 768 and 512 lanes): a table of no whole number of
    the walk's steps (6 of 32 blocks and 20 more)."""
    from ray_lightning_tpu.ops.paged_attention import paged_decode_attention

    W, M, N = 32, 212, 33 * 212 + 1
    assert "rlt_paged_decode" in _compile(
        lambda *a: paged_decode_attention(
            *a, n_head=64, n_kv_head=4, scale=192 ** -0.5),
        _sds((W, 64 * 192), jnp.bfloat16, one_chip),
        _sds((W, 768), jnp.bfloat16, one_chip),
        _sds((W, 512), jnp.bfloat16, one_chip),
        _sds((3, N, 32, 768), jnp.bfloat16, one_chip),
        _sds((3, N, 32, 512), jnp.bfloat16, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((W, M), jnp.int32, one_chip),
        _sds((W,), jnp.int32, one_chip),
    )


@pytest.mark.parametrize("n_kv_head,head_dim,layers,sink", [
    (8, 128, 6, False),     # K-EXAONE's window layers: rows 1024 / 1024
    (8, 192, 9, True),      # MiMo-V2-Flash's: rows 1536 / 1024, a sink
], ids=["k-exaone", "mimo"])
def test_ring_decode_kernel_compiles_at_serve_cell_shapes(
        one_chip, n_kv_head, head_dim, layers, sink):
    """64 query heads on 8 K/V heads over the window layers' ring pools
    of both share cells (32 slots, rings of 5 blocks of 32, the engine's
    32 x 5 + 1 blocks), values 128 wide, with and without a sink."""
    from ray_lightning_tpu.ops.paged_attention import ring_decode_attention

    W, R, N = MOE["W"], 5, MOE["W"] * 5 + 1
    hk, hv = n_kv_head * head_dim, n_kv_head * 128
    args = [_sds((W, 64 * head_dim), jnp.bfloat16, one_chip),
            _sds((W, hk), jnp.bfloat16, one_chip),
            _sds((W, hv), jnp.bfloat16, one_chip),
            _sds((layers, N, 32, hk), jnp.bfloat16, one_chip),
            _sds((layers, N, 32, hv), jnp.bfloat16, one_chip),
            _sds((), jnp.int32, one_chip),
            _sds((W, R), jnp.int32, one_chip),
            _sds((W,), jnp.int32, one_chip)]
    if sink:
        args.append(_sds((64,), jnp.float32, one_chip))
    assert "rlt_ring_decode" in _compile(
        lambda *a: ring_decode_attention(
            *a[:8], n_head=64, n_kv_head=n_kv_head, scale=head_dim ** -0.5,
            window=128, sink=a[8] if sink else None), *args)


def _moe_programs(one_chip):
    from ray_lightning_tpu.models import exaone_moe as em

    cfg = _moe_cfg()
    module = em.ExaoneMoE(cfg)

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda l: _sds(l.shape, l.dtype, one_chip), tree)

    params = abstract(jax.eval_shape(module.init_params,
                                     jax.random.PRNGKey(0)))
    cache = em.TwoKindKVCache(cfg, MOE["N"], MOE["Bs"], MOE["W"],
                              jnp.bfloat16)
    pool = abstract(jax.eval_shape(cache.init_pool))
    return module.serve_family(), params, pool, cache.window_blocks


@pytest.fixture(scope="module")
def moe_decode(one_chip):
    """The family's decode program as the engine jits it (pool donated)."""
    fam, params, pool, ring = _moe_programs(one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731

    def step(params, pool, full, rings, seq_lens, tokens):
        return fam.decode(params, pool, (full, rings), seq_lens, tokens)

    return jax.jit(step, donate_argnums=1).lower(
        params, pool, i32(MOE["W"], MOE["M"]), i32(MOE["W"], ring),
        i32(MOE["W"]), i32(MOE["W"])).compile()


def _footprint(compiled) -> float:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def test_moe_decode_step_names_its_kernels_and_fits(moe_decode):
    """The kernels' names are in the program (the window layers' ring
    kernel among them) and it leaves the chip (15.75 GB usable) over 2
    GB: 13.23 GB with the ring kernel, 11.96 of it weights."""
    text = moe_decode.as_text()
    for kernel in ("%rlt_moe_gate_up", "%rlt_moe_down", "%rlt_paged_decode",
                   "%rlt_ring_decode"):
        assert kernel in text
    assert 12e9 < _footprint(moe_decode) < 13.7e9


def test_moe_decode_step_gathers_no_ring(moe_decode):
    """The window layers' decode reads each slot's ring where it lies: no
    operation at all, in HBM or in VMEM (``S(1)``), yields the slots'
    gathered rings, with or without the current rows (the parent's
    program held 96, the relayout copies for its einsums among them)."""
    import re

    W = MOE["W"]
    found = re.findall(rf"= bf16\[{W},16[01],8,128\]", moe_decode.as_text())
    assert not found, len(found)


@pytest.mark.parametrize("opcode", ["copy", "convert", "gather",
                                    "dynamic-slice"])
def test_moe_decode_step_moves_no_pool_layer_and_no_expert_tensor(
        moe_decode, opcode):
    """No operation's result is a pool layer, a pool, every slot's whole
    table of positions, every slot's ring (the window layers' decode
    reads it where it lies, ``rlt_ring_decode``), or an expert-stacked
    weight tensor."""
    import re

    row, rings = 1024, MOE["W"] * 5 + 1
    N, Bs, W, M = MOE["N"], MOE["Bs"], MOE["W"], MOE["M"]
    d, f, E = MOE["d"], MOE["f"], MOE["E"]
    # By shape, not by size: W == Bs here, so a slot-major gather of the
    # rings (W, 161, 8, 128: 10 MB) is as large as a ring layer.
    moved = {(N, Bs, row), (2, N, Bs, row), (rings, Bs, row),
             (6, rings, Bs, row), (W, M, Bs, row), (W, M * Bs, row),
             (W, M * Bs, 8, 128), (W, 160, 8, 128), (W, 161, 8, 128),
             (E, d, f), (E, f, d), (d, f), (f, d)}
    # A result laid out in S(1) is XLA's own prefetch of an operand into
    # VMEM (the shared expert's down matrix), not a copy in HBM.
    rx = re.compile(
        r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\](\S*) ([\w\-]+)\(", re.M)
    found = [m.group(0).strip()[:160]
             for m in rx.finditer(moe_decode.as_text())
             if m.group(3).startswith(opcode) and "S(1)" not in m.group(2)
             and tuple(int(x) for x in m.group(1).split(",") if x != "1")
             in moved]
    assert not found, found


@pytest.fixture(scope="module")
def moe_prefill(one_chip):
    """The prefill at the cell's largest bucket, the pool donated."""
    fam, params, pool, ring = _moe_programs(one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731

    def prefill(params, pool, tokens, prompt_len, full, rings):
        return fam.prefill(params, pool, tokens, prompt_len, (full, rings))

    return jax.jit(prefill, donate_argnums=1).lower(
        params, pool, i32(3072), i32(), i32(3072 // MOE["Bs"]), i32(ring),
    ).compile()


def test_moe_prefill_fits_at_the_largest_bucket(moe_prefill):
    """Bucket 3072 leaves over 1 GB of the chip: 14.45 GB at PR 26
    (bucket 4096 needs 14.85 GB and is not used)."""
    text = moe_prefill.as_text()
    assert "%rlt_flash_fwd" in text and "%rlt_moe_gate_up" in text
    assert _footprint(moe_prefill) < 14.75e9


# -- the three decode programs against the parent's bytes (PR 35) -----------

@pytest.mark.parametrize("program,parent_bytes", [
    ("serve_decode", 4_766_827_008), ("moe_decode", 13_333_024_768),
    ("mla_decode", 13_262_705_664)])
def test_decode_programs_keep_the_parents_bytes(request, program,
                                                parent_bytes):
    """The walk's wider buffers live in VMEM (at most the compiler's
    default scoped limit for these bf16 pools): each cell's decode
    program needs no more HBM than it did before PR 35, to the byte
    (the same compile at PR 34's tree)."""
    assert _footprint(request.getfixturevalue(program)) <= parent_bytes


# -- the latent share (sarvam-105b-ep8.serve-longctx) -------------------------

# benchmarks/workloads/sarvam-105b-ep8.serve-longctx.json: 64 slots x 7168
# positions in blocks of 32, the engine's default pool of 65 x 224 + 1
# blocks of rows of 640 (576 of data); published widths, 16 of 128 experts.
MLA = dict(W=64, M=224, Bs=32, N=65 * 224 + 1, H=64, r=512, row=640)


def test_mla_decode_kernel_compiles_at_serve_cell_shapes(one_chip):
    """64 heads' absorbed queries against rows of 640, tables of 224
    blocks a slot by scalar prefetch (57 KB of SMEM)."""
    from ray_lightning_tpu.ops.paged_attention import mla_decode_attention

    assert "rlt_mla_decode" in _compile(
        lambda *a: mla_decode_attention(*a, rank=MLA["r"], scale=0.135234),
        _sds((MLA["W"], MLA["H"], MLA["row"]), jnp.bfloat16, one_chip),
        _sds((MLA["W"], MLA["row"]), jnp.bfloat16, one_chip),
        _sds((8, MLA["N"], MLA["Bs"], MLA["row"]), jnp.bfloat16, one_chip),
        _sds((), jnp.int32, one_chip),
        _sds((MLA["W"], MLA["M"]), jnp.int32, one_chip),
        _sds((MLA["W"],), jnp.int32, one_chip),
    )


def _mla_programs(one_chip):
    from ray_lightning_tpu.models import sarvam_mla as sm

    cfg = sm.SarvamMLAConfig(n_layer=8, experts_held=(0, 16),
                             vocab_held=(0, 32768))
    module = sm.SarvamMLA(cfg)
    fam = module.serve_family()

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda l: _sds(l.shape, l.dtype, one_chip), tree)

    raw = jax.eval_shape(module.init_params, jax.random.PRNGKey(0))
    params = abstract(jax.eval_shape(
        lambda tree: fam.prepare_params(tree, jnp.bfloat16), raw))
    cache = fam.make_cache(MLA["N"], MLA["Bs"], MLA["W"], jnp.bfloat16)
    return fam, params, abstract(jax.eval_shape(cache.init_pool))


@pytest.fixture(scope="module")
def mla_decode(one_chip):
    """The family's decode program as the engine jits it (pool donated)."""
    fam, params, pool = _mla_programs(one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    return jax.jit(fam.decode, donate_argnums=1).lower(
        params, pool, i32(MLA["W"], MLA["M"]), i32(MLA["W"]),
        i32(MLA["W"])).compile()


def test_mla_decode_step_names_its_kernels_and_fits(mla_decode):
    """The three kernels' names are in the program and it leaves the
    chip (15.75 GiB = 16.91 GB usable) 3.6 GB: 13.26 GB at PR 30, 8.45 of
    it weights and 4.77 the latent pool."""
    text = mla_decode.as_text()
    for kernel in ("%rlt_moe_gate_up", "%rlt_moe_down", "%rlt_mla_decode"):
        assert kernel in text
    assert "%rlt_paged_decode" not in text
    assert 13.0e9 < _footprint(mla_decode) < 13.5e9


@pytest.mark.parametrize("opcode", ["copy", "convert", "gather",
                                    "dynamic-slice"])
def test_mla_decode_step_moves_no_pool_layer_and_no_weight(mla_decode,
                                                           opcode):
    """No operation's result is a pool layer, the pool, every slot's
    whole table of rows, an up-projection (``W_uk`` / ``W_uv`` are the
    tree's own leaves, rearranged once at the engine's build) or an
    expert-stacked weight tensor."""
    import re

    N, Bs, W, M, row = (MLA[k] for k in ("N", "Bs", "W", "M", "row"))
    moved = {(N, Bs, row), (8, N, Bs, row), (W, M, Bs, row),
             (W, M * Bs, row), (64, 128, 512), (64, 512, 128),
             (512, 16384), (16, 4096, 2048), (16, 2048, 4096),
             (4096, 2048), (2048, 4096)}
    rx = re.compile(
        r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\](\S*) ([\w\-]+)\(", re.M)
    found = [m.group(0).strip()[:160]
             for m in rx.finditer(mla_decode.as_text())
             if m.group(3).startswith(opcode) and "S(1)" not in m.group(2)
             and tuple(int(x) for x in m.group(1).split(",") if x != "1")
             in moved]
    assert not found, found


def test_mla_prefill_fits_at_the_largest_bucket(one_chip):
    """Bucket 6144 (the cell's largest, past YaRN's original 4096)
    through the flash forward at head width 192 with values of 128:
    14.93 GB at PR 30, 2 GB of the chip left (the driver's reference
    check runs beside the engine, not beside this program)."""
    fam, params, pool = _mla_programs(one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    compiled = jax.jit(fam.prefill, donate_argnums=1).lower(
        params, pool, i32(6144), i32(), i32(6144 // MLA["Bs"])).compile()
    text = compiled.as_text()
    assert "%rlt_flash_fwd" in text and "%rlt_moe_gate_up" in text
    assert _footprint(compiled) < 15.05e9
