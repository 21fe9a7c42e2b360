"""The serve engine holds its weights in the dtype its programs read.

``GPTServeFamily.prepare_params`` casts, once at engine build, exactly
the leaves the paged programs would cast on every call; ``bf16(W)`` once
is bit for bit ``bf16(W)`` per call, so every comparison here is exact
(``np.array_equal``): no tolerance.  The ``exaone_moe`` family's tree is
handed on untouched and uncopied.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_tpu.models.exaone_moe import ExaoneMoE, exaone_moe_tiny
from ray_lightning_tpu.models.gpt import GPT, GPTConfig
from ray_lightning_tpu.models.quant import quantize_decode_params
from ray_lightning_tpu.serve.engine import ServeConfig, ServeEngine
from ray_lightning_tpu.serve.kv_cache import (
    TRASH_BLOCK, GPTServeFamily, PagedKVCache, paged_decode_step,
    paged_prefill, paged_verify_step,
)

pytestmark = pytest.mark.serve

BF16 = jnp.dtype(jnp.bfloat16)
F32 = jnp.dtype(jnp.float32)
# What the GPT programs read through ``.astype(compute_dtype)``.
CAST_BLOCK = {"qkv_w", "qkv_b", "proj_w", "proj_b",
              "mlp_in_w", "mlp_in_b", "mlp_out_w", "mlp_out_b"}
CAST_TOP = {"wte", "wpe"}


def _dense_cfg():
    return GPTConfig(vocab_size=128, n_layer=2, n_head=4, d_model=64,
                     seq_len=64, warmup_steps=1)


def _tree(kind):
    """(config, float32 or int8 tree) of a dense GPT, its int8 tree, or
    a GPT with routed experts."""
    if kind == "moe":
        cfg = GPTConfig.tiny_moe(n_experts=4, moe_capacity_factor=4.0)
    else:
        cfg = _dense_cfg()
    params = GPT(cfg, attn_impl="xla").init_params(jax.random.PRNGKey(0))
    if kind == "int8":
        params = quantize_decode_params(params, cfg)
    return cfg, params


@pytest.fixture(scope="module", params=["dense", "int8", "moe"])
def trees(request):
    cfg, params = _tree(request.param)
    return (request.param, cfg, params,
            GPTServeFamily(cfg).prepare_params(params, BF16))


@pytest.fixture(scope="module")
def bf16_model():
    m = GPT(_dense_cfg(), attn_impl="xla")
    m.precision = "bf16"
    return m, m.init_params(jax.random.PRNGKey(0))


def _jit(program, cfg):
    """A serving program as the engine runs it: jitted, in bfloat16."""
    return jax.jit(functools.partial(program, cfg, compute_dtype=BF16))


def _assert_same(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))


# -- (a) the programs: prepared tree against the tree as it came ------------

class TestProgramsAreExactlyEqual:
    def _pool(self, cfg):
        cache = PagedKVCache(cfg, num_blocks=8, block_size=8, dtype=BF16)
        return cache.init_pool()

    def _prefilled(self, cfg, params):
        toks = np.zeros((16,), np.int32)
        toks[:11] = np.arange(1, 12)
        return _jit(paged_prefill, cfg)(
            params, self._pool(cfg), jnp.asarray(toks), jnp.int32(11),
            jnp.asarray([3, 5], jnp.int32))

    def test_prefill(self, trees):
        _, cfg, params, prepared = trees
        _assert_same(self._prefilled(cfg, prepared),
                     self._prefilled(cfg, params))

    def test_decode_step(self, trees):
        _, cfg, params, prepared = trees
        _, pool = self._prefilled(cfg, params)
        tables = np.full((2, 4), TRASH_BLOCK, np.int32)
        tables[0, :2] = [3, 5]
        args = (jnp.asarray(tables), jnp.asarray([11, 0], jnp.int32),
                jnp.asarray([7, 0], jnp.int32))
        step = _jit(paged_decode_step, cfg)
        _assert_same(step(prepared, pool, *args), step(params, pool, *args))

    def test_verify_step(self, trees):
        _, cfg, params, prepared = trees
        _, pool = self._prefilled(cfg, params)
        tables = np.full((2, 4), TRASH_BLOCK, np.int32)
        tables[0, :2] = [3, 5]
        args = (jnp.asarray(tables), jnp.asarray([11, 0], jnp.int32),
                jnp.asarray([[7, 9, 4], [0, 0, 0]], jnp.int32),
                jnp.asarray([14, 0], jnp.int32))
        step = _jit(paged_verify_step, cfg)
        _assert_same(step(prepared, pool, *args), step(params, pool, *args))


# -- what is cast and what is left alone ------------------------------------

class TestWhatIsCast:
    def test_only_the_leaves_the_programs_cast(self, trees):
        kind, _, params, prepared = trees
        for name, leaf in prepared["blocks"].items():
            was = params["blocks"][name]
            if name in CAST_BLOCK or (name.endswith("_w_sc")
                                      and not name.startswith("moe_")):
                assert leaf.dtype == BF16, name
            else:
                # LayerNorm, int8 storage, the router and the experts.
                assert leaf is was, name
        for name, leaf in prepared.items():
            if name == "blocks":
                continue
            if name in CAST_TOP or name == "wte_sc":
                assert leaf.dtype == BF16, name
            else:
                assert leaf is params[name], name
        if kind == "int8":
            assert prepared["blocks"]["qkv_w_q8"].dtype == jnp.int8
            assert prepared["wte_q8"].dtype == jnp.int8
        if kind == "moe":
            assert prepared["blocks"]["gate_w"].dtype == F32
            assert prepared["blocks"]["moe_in_w"].dtype == F32

    def test_at_float32_the_tree_is_returned_itself(self, trees):
        _, cfg, params, _ = trees
        assert GPTServeFamily(cfg).prepare_params(params, F32) is params

    def test_a_prepared_tree_is_returned_itself(self, trees):
        _, cfg, _, prepared = trees
        assert GPTServeFamily(cfg).prepare_params(prepared, BF16) is prepared

    def test_a_leaf_in_its_dtype_is_not_copied(self):
        cfg, params = _tree("dense")
        params = dict(params, wte=params["wte"].astype(BF16))
        prepared = GPTServeFamily(cfg).prepare_params(params, BF16)
        assert prepared["wte"] is params["wte"]
        assert prepared["wpe"].dtype == BF16


class TestExaoneFamilyLeavesItsTreeAlone:
    @pytest.fixture(scope="class")
    def tiny(self):
        cfg = exaone_moe_tiny(experts_held=(4, 8), vocab_held=(0, 128),
                              param_dtype="bfloat16")
        module = ExaoneMoE(cfg)
        return module, module.init_params(jax.random.PRNGKey(0))

    def test_prepared_leaves_are_the_buffers_passed_in(self, tiny):
        module, params = tiny
        prepared = module.serve_family().prepare_params(
            params, module._compute_dtype())
        assert prepared is params

    def test_engine_holds_the_same_buffers_and_a_float32_router(self, tiny):
        module, params = tiny
        eng = ServeEngine(module, params, ServeConfig(
            num_slots=2, block_size=4, max_model_len=32))
        try:
            held, came = (jax.tree.leaves(eng.params),
                          jax.tree.leaves(params))
            assert len(held) == len(came)
            for a, b in zip(held, came):
                assert (a.unsafe_buffer_pointer()
                        == b.unsafe_buffer_pointer())
            routers = [p["router"] for p in eng.params["layers"]
                       if "router" in p]
            assert routers and all(r.dtype == F32 for r in routers)
            assert eng.params["embed"].dtype == BF16
            assert eng.stats.counters["weights_cast_leaves"] == 0
            assert eng.stats.counters["weights_resident_bytes"] == sum(
                leaf.nbytes for leaf in came)
        finally:
            eng.stop()


# -- (b), (f) the engine ------------------------------------------------------

def _serve(m, params, requests, together):
    """Tokens of ``requests`` [(prompt, n, temperature)] from a fresh
    engine, one at a time or all submitted before the first step."""
    eng = ServeEngine(m, params, ServeConfig(num_slots=4, block_size=8))
    try:
        if not together:
            return [eng.generate(p, n, temperature=t)
                    for p, n, t in requests]
        handles = [eng.submit(p, n, temperature=t) for p, n, t in requests]
        eng.run_until_idle()
        return [h.result(5) for h in handles]
    finally:
        eng.stop()


def _requests(temperature):
    rng = np.random.default_rng(3)
    return [(rng.integers(1, 128, size=(n,)).tolist(), 9, temperature)
            for n in (5, 11, 17)]


class TestEngine:
    @pytest.fixture(scope="class")
    def served(self, bf16_model):
        """``served(prepared, temperature, together)``: the tokens of
        ``_requests(temperature)``, each combination from a fresh engine
        and served once for the cases that compare it."""
        m, params = bf16_model
        trees = {False: params,
                 True: GPTServeFamily(m.config).prepare_params(params, BF16)}

        @functools.cache
        def serve(prepared, temperature, together):
            return _serve(m, trees[prepared], _requests(temperature),
                          together)

        return serve

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    @pytest.mark.parametrize("together", [False, True],
                             ids=["alone", "batched"])
    def test_float32_and_prepared_tree_serve_the_same_tokens(
            self, served, temperature, together):
        assert (served(False, temperature, together)
                == served(True, temperature, together))

    def test_same_alone_and_batched(self, served):
        assert served(False, 0.0, False) == served(False, 0.0, True)

    @pytest.mark.parametrize("kind,precision,cast", [
        ("dense", "bf16", 10), ("dense", "32", 0),
        ("moe", "bf16", 6), ("moe", "32", 0)])
    def test_counters_read_what_the_tree_says(self, kind, precision, cast):
        cfg, params = _tree(kind)
        m = GPT(cfg, attn_impl="xla")
        m.precision = precision
        eng = ServeEngine(m, params, ServeConfig(num_slots=2, block_size=8))
        try:
            counters = eng.stats.counters
            assert counters["weights_cast_leaves"] == cast
            assert counters["weights_resident_bytes"] == sum(
                leaf.nbytes for leaf in jax.tree.leaves(eng.params))
            changed = sum(a.dtype != b.dtype for a, b in zip(
                jax.tree.leaves(eng.params), jax.tree.leaves(params)))
            assert changed == cast
            if cast:
                assert counters["weights_resident_bytes"] < sum(
                    leaf.nbytes for leaf in jax.tree.leaves(params))
            assert eng.snapshot()["counters"]["weights_cast_leaves"] == cast
        finally:
            eng.stop()

    def test_draft_tree_is_prepared_too(self, bf16_model):
        from ray_lightning_tpu.serve.draft import early_exit_draft

        m, params = bf16_model
        draft, dparams = early_exit_draft(m, params, 1)
        draft.precision = "bf16"
        eng = ServeEngine(m, params, ServeConfig(
            num_slots=2, block_size=8, spec_k=2),
            draft_module=draft, draft_params=dparams)
        try:
            assert eng.draft_params["blocks"]["qkv_w"].dtype == BF16
            assert eng.draft_params["blocks"]["ln1_g"].dtype == F32
            plain = _serve(m, params, _requests(0.0)[:1], False)
            assert [eng.generate(*_requests(0.0)[0][:2])] == plain
        finally:
            eng.stop()


# -- (c) no weight is converted inside the programs -------------------------

def _weight_converts(text, cfg):
    """``convert`` operations of the lowered program whose operand has a
    weight's shape: the whole stack, or one layer's slice of it inside
    the scan."""
    L, d, h = cfg.n_layer, cfg.d_model, cfg.mlp_ratio * cfg.d_model
    per_layer = [(d, 3 * d), (d, d), (d, h), (h, d)]
    shapes = {"x".join(map(str, s)) for s in per_layer}
    shapes |= {"x".join(map(str, (L,) + s)) for s in per_layer}
    shapes |= {f"{cfg.vocab_size}x{d}", f"{cfg.seq_len}x{d}"}
    rx = re.compile(r"stablehlo\.convert [^\n]*\(tensor<([\dx]+)xf32>\)"
                    r" -> tensor<[\dx]+xbf16>")
    return [m.group(0) for m in rx.finditer(text) if m.group(1) in shapes]


class TestNoConvertOfAWeight:
    def _lowered(self, m, params):
        """The engine's ``_decode`` and ``_prefill`` lowered with the
        operands of a real request."""
        eng = ServeEngine(m, params, ServeConfig(num_slots=2, block_size=8))
        fns = {"decode": eng._decode_fn, "prefill": eng._prefill_fn}
        seen = {}

        def spy(name):
            def call(*args):
                seen.setdefault(name, args)
                return fns[name](*args)
            return call

        eng._decode_fn, eng._prefill_fn = spy("decode"), spy("prefill")
        try:
            eng.generate(list(range(1, 12)), 3)
            return {name: fn.lower(*seen[name]).as_text()
                    for name, fn in fns.items()}
        finally:
            eng.stop()

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_bf16_engine_converts_no_weight(self, bf16_model, program):
        m, params = bf16_model
        text = self._lowered(m, params)[program]
        assert "stablehlo.convert" in text       # activations still are
        assert not _weight_converts(text, m.config)

    def test_the_reading_sees_a_convert_where_there_is_one(self, bf16_model):
        """The float32 tree handed to the program itself, as the engine
        did before: the same reading finds the weights' converts."""
        m, params = bf16_model
        cfg = m.config
        pool = PagedKVCache(cfg, 8, 8, dtype=BF16).init_pool()
        text = jax.jit(
            lambda p, pool, t, s, tok: paged_decode_step(
                cfg, p, pool, t, s, tok, compute_dtype=BF16)
        ).lower(params, pool, jnp.zeros((2, 4), jnp.int32),
                jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), jnp.int32)).as_text()
        assert len(_weight_converts(text, cfg)) >= 5
