"""What only the ``mimo_v2`` family has, against its plain reference
(``benchmarks/reference/mimo_v2_ref.py``) at the tiny preset (7 layers
``F S S S S F S``, layer 0 dense, 16 experts top-4, 4 held, window 8, 8
query heads over 2 / 4 K/V heads, keys 12 wide of which 4 rotate, values
8 wide, float32 weights): the attention geometry each mixer kind carries
as data, and each departure from it failing the parity tolerance.
What every served family is held to (forward, prefill then decode
through the two-kind pool past the window, the engine, the shares, the
cell's rehearsal) is in ``test_serve_families.py``, row ``MiMo``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from ray_lightning_tpu.models import mimo_v2 as mm
from test_serve_families import F32_TOL, REF_LEN, MiMo
from utils import draw_tokens, reference_logits, tiny_family


@pytest.fixture(scope="module")
def tiny():
    return tiny_family(MiMo.preset, MiMo.Module, MiMo.gains)


def test_published_pattern_and_geometry_are_the_defaults():
    cfg = mm.MiMoV2Config()
    assert [i for i, t in enumerate(cfg.layer_types) if t == "full"] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 47
    full, ring = cfg.geometry("full"), cfg.geometry("sliding")
    assert (full.n_kv_head, full.head_dim, full.v_head_dim, full.rope_dim,
            full.rope_theta, full.window, full.sink) == (
        4, 192, 128, 64, 5e6, None, False)
    assert (ring.n_kv_head, ring.head_dim, ring.v_head_dim, ring.rope_dim,
            ring.rope_theta, ring.window, ring.sink) == (
        8, 192, 128, 64, 1e4, 128, True)
    assert full.value_scale == ring.value_scale == 0.707
    assert not full.qk_norm and not ring.qk_norm
    # The full layers' pool row is 2,560 bytes in bf16; a ring's 5,120.
    assert (full.k_width + full.v_width) * 2 == 2560
    assert (ring.k_width + ring.v_width) * 2 == 5120


def test_the_two_kind_pool_holds_each_kinds_widths(tiny):
    cfg = tiny[0]
    cache = mm.MiMoV2(cfg).serve_family().make_cache(9, 4, 2, jnp.float32)
    pool = jax.eval_shape(cache.init_pool)
    assert pool["k"].shape == (2, 9, 4, 2 * 12)
    assert pool["v"].shape == (2, 9, 4, 2 * 8)
    assert pool["wk"].shape == (5, 2 * 3 + 1, 4, 4 * 12)
    assert pool["wv"].shape == (5, 2 * 3 + 1, 4, 4 * 8)


def _departed(name, cfg, params):
    """A program that leaves out one piece of the published geometry:
    ``(config, params)`` it would run."""
    if name == "sinks_dropped":
        # A sink whose logit is -inf takes no share: no sink at all.
        layers = [dict(p, sink=jnp.full_like(p["sink"], -jnp.inf))
                  if "sink" in p else p for p in params["layers"]]
        return cfg, dict(params, layers=layers)
    if name == "all_columns_rotated":
        return dataclasses.replace(cfg, rope_dim=cfg.head_dim), params
    if name == "value_scale_dropped":
        return dataclasses.replace(cfg, value_scale=1.0), params
    assert name == "one_kv_head_count"
    # The window layers read with the full layers' K/V head count: the
    # first heads of their K and V projections.
    one = dataclasses.replace(cfg, swa_n_kv_head=cfg.n_kv_head)
    g = one.geometry("sliding")
    layers = [dict(p, wk=p["wk"][:, :g.k_width], wv=p["wv"][:, :g.v_width])
              if kind == "sliding" else p
              for p, kind in zip(params["layers"], cfg.layer_types)]
    return one, dict(params, layers=layers)


@pytest.mark.parametrize("name", ["sinks_dropped", "all_columns_rotated",
                                  "value_scale_dropped",
                                  "one_kv_head_count"])
def test_each_departure_fails_the_parity_tolerance(tiny, name):
    """The tolerance every family is held to (``F32_TOL``) is tight
    enough to see each piece of the geometry: a program without it is
    over fifty times the tolerance from the reference past the window."""
    cfg, module, params = tiny
    toks = draw_tokens(40)
    want, _ = reference_logits(MiMo.ref, cfg, params, toks, REF_LEN)
    got = jax.jit(module.forward)(params, toks[None])[0]
    assert float(jnp.abs(got - want).max()) < F32_TOL
    dcfg, dparams = _departed(name, cfg, params)
    got = jax.jit(mm.MiMoV2(dcfg).forward)(dparams, toks[None])[0]
    assert float(jnp.abs(got - want).max()) > 50 * F32_TOL

