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


def _tables(rows):
    bt = np.full((W, M), TRASH_BLOCK, np.int32)
    for w, ids in enumerate(rows):
        bt[w, :len(ids)] = ids
    return bt


def _own_blocks(w, n=M):
    return [1 + w * M + j for j in range(n)]


def _scenario(name):
    """``(block_tables, seq_lens, write_limit, slots whose logits count)``."""
    every = list(range(W))
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
              "write_limit_past_table"]
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


def _filled_pool(cfg, dtype, block_size=BS):
    cache = PagedKVCache(cfg, num_blocks=N, block_size=block_size,
                         dtype=dtype)
    shape = cache.init_pool()["k"].shape
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    return {"k": jax.random.normal(kk, shape).astype(dtype),
            "v": jax.random.normal(kv, shape).astype(dtype)}


def _step(cfg, params, pool, scenario, dtype, impl):
    bt, lens, limit, _ = scenario
    return paged_decode_step(
        cfg, params, pool, jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
        jnp.arange(1, W + 1, dtype=jnp.int32), compute_dtype=dtype,
        write_limit=None if limit is None else jnp.asarray(limit, jnp.int32),
        attn_impl=impl,
    )


@pytest.mark.parametrize("width", [128, 1280])
@pytest.mark.parametrize("dtype", sorted(_DTYPES))
@pytest.mark.parametrize("name", _SCENARIOS)
def test_kernel_matches_xla_path(models, name, dtype, width):
    cfg, params = models[width]
    dt = _DTYPES[dtype]
    scenario = _scenario(name)
    pool = _filled_pool(cfg, dt)
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
        # ... and the step wrote W rows a layer, nothing else.
        changed = (a != np.asarray(pool[key].astype(jnp.float32))[:, 1:])
        assert changed.any(axis=-1).sum() <= cfg.n_layer * W


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
