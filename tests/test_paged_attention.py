"""``rlt_paged_decode`` against the XLA gather of ``paged_decode_step``.

The kernel runs under the Pallas interpreter here (``attn_impl="pallas"``
on the CPU); ``tests/test_chip_compile.py`` compiles it for the chip at
the serving cell's shapes.  Both paths see the same two-layer model, the
same randomly filled pool and the same tables: the logits agree within
the tolerance ``tests/test_ops_flash.py`` holds flash to against XLA,
and the pools agree after the step (the trash block aside: several slots may
write it, and which write lands last is nobody's contract).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPT, GPTConfig
from ray_lightning_tpu.ops import paged_attention
from ray_lightning_tpu.serve.kv_cache import (
    TRASH_BLOCK, PagedKVCache, paged_decode_step,
)

pytestmark = pytest.mark.serve

W, BS, M, N = 6, 16, 4, 32
S = M * BS
DH = 64
# The walk's tables: four steps of the kernel's width, so a slot walks
# one to four chunks, the last one trimmed.
WIDE = paged_attention.CHUNK_POSITIONS
M_WALK = 4 * WIDE // BS


def _tables(rows, m=M):
    bt = np.full((len(rows), m), TRASH_BLOCK, np.int32)
    for w, ids in enumerate(rows):
        bt[w, :len(ids)] = ids
    return bt


def _own_blocks(w, n=M, m=M):
    return [1 + w * m + j for j in range(n)]


def _walk(lens, m=M_WALK):
    """Tables of ``m`` entries, each slot the blocks its length and the
    new token need (at most the table), the rest trash."""
    return _tables([_own_blocks(w, min(-(-(n + 1) // BS), m) if n else 0, m)
                    for w, n in enumerate(lens)], m)


def _scenario(name):
    """``(block_tables, seq_lens, write_limit, slots whose logits count)``."""
    every = list(range(W))
    if name == "walk_short_edges":
        # One chunk: the trimmed tail of one, one and two sub-tiles.
        lens = [1, BS - 1, BS, 127, 128, 129]
        return _walk(lens), lens, None, every
    if name == "walk_long_edges":
        # A chunk's edges, two whole chunks and a trimmed third, the
        # table's last position, and an idle slot between two long ones
        # (the next slot's first chunk is in flight across it).
        lens = [WIDE - 1, WIDE, 0, WIDE + 1, 2 * WIDE + 129, 300,
                M_WALK * BS - 1]
        return _walk(lens), lens, None, [0, 1, 3, 4, 5, 6]
    if name == "walk_past_table":
        # The draft chain on the walk's table: slots past their table
        # (a walk clamped to it, the write to trash), each followed by a
        # live slot whose first chunk is in flight while the stray's
        # tail is computed.  Strays' logits are never used (the XLA
        # path leaves out their own token, written to trash).
        end = M_WALK * BS
        lens = [end + 3, 2 * WIDE + 129, end + WIDE + 1, WIDE + 1, 5]
        return _walk(lens), lens, [end] * len(lens), [1, 3, 4]
    if name == "walk_ragged_table":
        # A table of no whole number of steps (253 blocks: 3 steps and
        # 61 blocks): a full slot's last chunk is what is left of the
        # table, a stray past it walks to its end; the idle slot writes
        # trash, which the XLA path reads back.
        m = M_WALK - 3
        end = m * BS
        lens = [end - 1, 3 * WIDE + 5, end + 7, 3 * WIDE - 1, 0, 129]
        return (_walk(lens, m), lens, [end] * len(lens), [0, 1, 3, 5])
    if name == "length_edges":
        lens = [0, 1, BS - 1, BS, BS + 1, S - 1]
        return _tables([_own_blocks(w) for w in every]), lens, None, every
    if name == "inactive_all_trash":
        rows = [_own_blocks(w) for w in every]
        rows[2] = []
        rows[4] = []
        # Both inactive slots write the trash block's first row and the
        # XLA path reads it back: their logits are nobody's.
        return (_tables(rows), [37, 5, 0, BS, 0, 2 * BS + 3], None,
                [0, 1, 3, 5])
    if name == "shared_prefix":
        shared = [25, 26]
        rows = [shared + [27, 28], shared + [29], shared[:1] + [30, 31],
                _own_blocks(0), _own_blocks(1, 2), []]
        return (_tables(rows), [3 * BS + 5, 2 * BS + 9, BS, 20, BS + 1, 0],
                None, every)
    if name == "write_limit_past_table":
        # The draft chain: slot 0 runs past its table (clamped walk,
        # write to trash), slot 1 sits at its limit (write to trash),
        # the rest are inside theirs.  Strays' logits are never used.
        lens = [S + 3, 5, BS, 2 * BS - 1, S - 1, 0]
        limit = [S, 5, 2 * BS, 2 * BS, S, 0]
        return (_tables([_own_blocks(w) for w in every]), lens, limit,
                [2, 3, 4])
    raise AssertionError(name)


_SCENARIOS = ["length_edges", "inactive_all_trash", "shared_prefix",
              "write_limit_past_table", "walk_short_edges", "walk_long_edges",
              "walk_past_table", "walk_ragged_table"]
# dtype of the pool and of the compute; logits tolerance as flash's:
# absolute 1e-5 in float32, 1e-2 of the largest logit in bfloat16.
_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


@pytest.fixture(scope="module")
def models():
    out = {}
    for n_head in (2, 20):
        cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=n_head,
                        d_model=n_head * DH, seq_len=S, warmup_steps=1)
        m = GPT(cfg, attn_impl="xla")
        out[n_head * DH] = (cfg, m.init_params(jax.random.PRNGKey(0)))
    return out


def _filled_pool(cfg, dtype, block_size=BS, num_blocks=N):
    cache = PagedKVCache(cfg, num_blocks=num_blocks, block_size=block_size,
                         dtype=dtype)
    shape = cache.init_pool()["k"].shape
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    return {"k": jax.random.normal(kk, shape).astype(dtype),
            "v": jax.random.normal(kv, shape).astype(dtype)}


def _step(cfg, params, pool, scenario, dtype, impl):
    bt, lens, limit, _ = scenario
    return paged_decode_step(
        cfg, params, pool, jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
        jnp.arange(1, len(lens) + 1, dtype=jnp.int32), compute_dtype=dtype,
        write_limit=None if limit is None else jnp.asarray(limit, jnp.int32),
        attn_impl=impl,
    )


# The walk's long tables at the small row only.
@pytest.mark.parametrize("name,dtype,width", [
    (name, dtype, width) for name in _SCENARIOS for dtype in sorted(_DTYPES)
    for width in (128, 1280) if width == 128 or not name.startswith("walk")])
def test_kernel_matches_xla_path(models, name, dtype, width):
    cfg, params = models[width]
    dt = _DTYPES[dtype]
    scenario = _scenario(name)
    pool = _filled_pool(cfg, dt, num_blocks=int(scenario[0].max()) + 1)
    want, want_pool = _step(cfg, params, pool, scenario, dt, "xla")
    got, got_pool = _step(cfg, params, pool, scenario, dt, "pallas")
    used = scenario[3]
    want, got = np.asarray(want)[used], np.asarray(got)[used]
    assert np.isfinite(got).all()
    gap = np.abs(got - want).max()
    if dt == jnp.float32:
        assert gap < 1e-5, gap
    else:
        assert gap / max(np.abs(want).max(), 1.0) < 1e-2, gap
    for key in ("k", "v"):
        a = np.asarray(got_pool[key].astype(jnp.float32))[:, 1:]
        b = np.asarray(want_pool[key].astype(jnp.float32))[:, 1:]
        # Layer 0's rows come from identical inputs; layer 1's sit
        # behind layer 0's attention, so they agree as the logits do.
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_allclose(
            a[1], b[1], rtol=0, atol=1e-5 if dt == jnp.float32 else 2e-2
        )
        # ... and the step wrote a row a slot a layer, nothing else.
        changed = (a != np.asarray(pool[key].astype(jnp.float32))[:, 1:])
        assert changed.any(axis=-1).sum() <= cfg.n_layer * len(scenario[1])


@pytest.mark.parametrize("name,dtype", [
    (name, dtype) for name in ("length_edges", "walk_past_table",
                               "walk_ragged_table")
    for dtype in sorted(_DTYPES)])
def test_grouped_kernel_reads_values_of_their_own_width(name, dtype):
    """64 query heads on 4 K/V heads, keys 192 and values 128 wide (the
    MiMo-V2-Flash full layers' rows of 768 and 512 lanes): the kernel
    under the interpreter against the XLA gather of the pattern
    decoder's decode (``exaone_moe.attend_one``), the walk's strays past
    their table among the slots."""
    from ray_lightning_tpu.models.exaone_moe import AttnGeometry, attend_one

    H, g = 64, AttnGeometry(4, 192, 128, rope_dim=0, rope_theta=1.0,
                            window=None)
    dt = _DTYPES[dtype]
    bt, lens, _, used = _scenario(name)
    W_, M_ = bt.shape
    n = int(bt.max()) + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    k_pool = jax.random.normal(ks[0], (1, n, BS, g.k_width)).astype(dt)
    v_pool = jax.random.normal(ks[1], (1, n, BS, g.v_width)).astype(dt)
    q = jax.random.normal(ks[2], (W_, H * g.head_dim)).astype(dt)
    k_cur = jax.random.normal(ks[3], (W_, g.k_width)).astype(dt)
    v_cur = jax.random.normal(ks[4], (W_, g.v_width)).astype(dt)
    tables, seq = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
    got = paged_attention.paged_decode_attention(
        q, k_cur, v_cur, k_pool, v_pool, jnp.int32(0), tables, seq,
        n_head=H, n_kv_head=g.n_kv_head, scale=g.head_dim ** -0.5)

    def ctx(pool, row, width):
        rows = pool[0][tables].reshape(W_, M_ * BS, -1)
        rows = jnp.concatenate([rows, row[:, None]], axis=1)
        return rows.reshape(W_, M_ * BS + 1, g.n_kv_head, width)

    visible = jnp.concatenate(
        [jnp.arange(M_ * BS)[None, :] < seq[:, None],
         jnp.ones((W_, 1), bool)], axis=1)
    want = attend_one(g, q.reshape(W_, H, g.head_dim),
                      ctx(k_pool, k_cur, g.head_dim),
                      ctx(v_pool, v_cur, g.v_head_dim), visible)
    assert got.shape == (W_, H * g.v_head_dim)
    want, got = np.asarray(want)[used], np.asarray(got)[used]
    assert np.isfinite(got).all()
    gap = np.abs(got - want).max()
    assert gap < (1e-5 if dt == jnp.float32 else 1e-2), gap


def _walk_by_hand(n, m, bs, wide):
    """The kernel's walk of one slot of ``n`` positions, step by step:
    chunks of ``wide`` positions (at most the table, at least a block),
    every one whole but the last, which computes the 128-position
    sub-tiles holding the slot's positions (the whole chunk where 128
    does not divide it)."""
    n = min(n, m * bs)
    pages = max(1, min(m, wide // bs))
    width = pages * bs
    tail = 128 if width % 128 == 0 else width
    steps = positions = 0
    start = 0
    while start < n:
        if start + width < n:
            steps, positions = steps + 1, positions + width
        else:
            held = n - start
            steps += 1
            positions += tail * -(-held // tail)
        start += width
    return steps, positions


@pytest.mark.parametrize("wide", [128, 256, 512, 1024])
@pytest.mark.parametrize("table", [(32, 32), (96, 16), (224, 32), (6, 16),
                                   (212, 32)],
                         ids=["gpt2_cell", "walk", "sarvam", "narrow",
                              "ragged"])
def test_walk_plan_counts_the_walk(monkeypatch, table, wide):
    """``walk_plan`` against the walk counted slot by slot, at every
    length from 0 to the table's end and past it (clamped), at the
    kernels' width and others."""
    monkeypatch.setattr(paged_attention, "CHUNK_POSITIONS", wide)
    m, bs = table
    lens = np.arange(0, m * bs + 3 * bs)
    want = [_walk_by_hand(int(n), m, bs, wide) for n in lens]
    assert paged_attention.walk_plan(lens, m, bs) == (
        sum(s for s, _ in want), sum(p for _, p in want))
    for n, one in zip(lens[::7], want[::7]):
        assert paged_attention.walk_plan([n], m, bs) == one
    # The masked tail a slot computes is under a sub-tile.
    for n, (_, p) in zip(lens, want):
        if n:
            assert p - min(n, m * bs) < 128


_UNTILED = {
    "row_of_64": dict(n_head=1, block_size=16, dtype=jnp.float32),
    "bf16_block_of_8": dict(n_head=2, block_size=8, dtype=jnp.bfloat16),
    "float16_pool": dict(n_head=2, block_size=16, dtype=jnp.float16),
}


@pytest.mark.parametrize("case", sorted(_UNTILED))
def test_untiled_shape_takes_the_xla_path(monkeypatch, case):
    """What the kernel does not tile goes to the XLA gather on a TPU too
    (``auto`` reads backend, shape and dtype), gives the same tokens, and
    is refused by name when the kernel is asked for outright."""
    spec = _UNTILED[case]
    cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=spec["n_head"],
                    d_model=spec["n_head"] * DH, seq_len=S, warmup_steps=1)
    params = GPT(cfg, attn_impl="xla").init_params(jax.random.PRNGKey(0))
    pool = _filled_pool(cfg, spec["dtype"], spec["block_size"])
    bt = np.full((W, S // spec["block_size"]), TRASH_BLOCK, np.int32)
    bt[:, :2] = np.arange(1, 2 * W + 1).reshape(W, 2)
    scenario = (bt, [0, 1, 7, 8, 9, 15], None, None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not paged_attention.paged_decode_supported(pool["k"])
    auto, _ = _step(cfg, params, pool, scenario, jnp.float32, "auto")
    xla, _ = _step(cfg, params, pool, scenario, jnp.float32, "xla")
    np.testing.assert_array_equal(
        np.asarray(auto).argmax(-1), np.asarray(xla).argmax(-1)
    )
    with pytest.raises(ValueError, match="rlt_paged_decode does not tile"):
        _step(cfg, params, pool, scenario, jnp.float32, "pallas")


def test_auto_selection_reads_backend_and_disable_switch(monkeypatch, models):
    cfg, _ = models[128]
    pool_k = jax.ShapeDtypeStruct((2, N, BS, cfg.d_model), jnp.bfloat16)
    assert not paged_attention.paged_decode_supported(pool_k)  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert paged_attention.paged_decode_supported(pool_k)
    monkeypatch.setenv("RLT_DISABLE_KERNELS", "flash,paged")
    assert not paged_attention.paged_decode_supported(pool_k)
    with pytest.raises(ValueError, match="Unknown paged attention impl"):
        paged_decode_step(cfg, None, {"k": pool_k, "v": pool_k}, None,
                          None, None, attn_impl="flash")
