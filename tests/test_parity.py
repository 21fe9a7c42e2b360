"""Gradient/loss parity across execution flavors — the north-star metric's
second half ("DDP↔pmap gradient parity").

Single-device vs GSPMD-sharded vs shard_map-explicit must produce the same
gradients and the same training trajectory on a fixed seed/batch, within
fp32 tolerance (SURVEY §7 hard-part #5: bitwise equality is not achievable
across different collective schedules; 1e-5 rel is).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from ray_lightning_tpu.core.loop import init_train_state
from ray_lightning_tpu.core.module import TrainState
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.parallel import step_fns
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.sharding import make_global_batch


@pytest.fixture
def setup():
    module = BoringModel(in_dim=16, out_dim=4, lr=0.1)
    tx = module.configure_optimizers()
    rng = jax.random.PRNGKey(0)
    batch = {"x": np.random.default_rng(0).standard_normal(
        (16, 16), dtype=np.float32)}
    return module, tx, rng, batch


def _run_steps(module, tx, rng, batch, mesh, mode, zero_stage=0, n=3):
    state, shardings = init_train_state(module, tx, mesh, zero_stage, seed=0)
    step = step_fns.build_train_step(
        module, tx, mesh, mode=mode, state_shardings=shardings
    )
    placed = batch if mesh is None else make_global_batch(batch, mesh)
    losses = []
    for i in range(n):
        state, logs = step(state, placed, jax.random.fold_in(rng, i))
        losses.append(float(logs["loss"]))
    return jax.device_get(state.params), losses


def _assert_close(pa, pb, tol=1e-5):
    la, lb = jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(x, y, rtol=tol, atol=tol)


def test_gspmd_matches_single_device(setup):
    module, tx, rng, batch = setup
    p_single, l_single = _run_steps(module, tx, rng, batch, None, "gspmd")
    mesh = build_mesh(MeshSpec())
    p_mesh, l_mesh = _run_steps(module, tx, rng, batch, mesh, "gspmd")
    _assert_close(p_single, p_mesh)
    np.testing.assert_allclose(l_single, l_mesh, rtol=1e-5)


def test_shard_map_matches_single_device(setup):
    module, tx, rng, batch = setup
    p_single, _ = _run_steps(module, tx, rng, batch, None, "gspmd")
    mesh = build_mesh(MeshSpec())
    p_sm, _ = _run_steps(module, tx, rng, batch, mesh, "shard_map")
    _assert_close(p_single, p_sm)


def test_zero1_matches_replicated(setup):
    module, tx, rng, batch = setup
    mesh = build_mesh(MeshSpec())
    p_repl, _ = _run_steps(module, tx, rng, batch, mesh, "gspmd", 0)
    p_z1, _ = _run_steps(module, tx, rng, batch, mesh, "gspmd", 1)
    _assert_close(p_repl, p_z1)


def test_zero3_matches_replicated(setup):
    module, tx, rng, batch = setup
    mesh = build_mesh(MeshSpec())
    p_repl, _ = _run_steps(module, tx, rng, batch, mesh, "gspmd", 0)
    p_z3, _ = _run_steps(module, tx, rng, batch, mesh, "gspmd", 3)
    _assert_close(p_repl, p_z3)


def test_zero3_actually_shards_large_params():
    """ZeRO-3 must physically partition big leaves over the mesh."""
    module = BoringModel(in_dim=256, out_dim=128)
    tx = module.configure_optimizers()
    mesh = build_mesh(MeshSpec())
    state, shardings = init_train_state(module, tx, mesh, 3, seed=0)
    w = state.params["w"]  # (256, 128) = 32768 elems > min_leaf_size
    assert not w.sharding.is_fully_replicated
    # Each device holds 1/8 of the rows.
    shard_shape = w.sharding.shard_shape(w.shape)
    assert shard_shape[0] * 8 == 256 or shard_shape[1] * 8 == 128


def test_loss_decreases(setup):
    module, tx, rng, batch = setup
    mesh = build_mesh(MeshSpec())
    _, losses = _run_steps(module, tx, rng, batch, mesh, "gspmd", n=10)
    assert losses[-1] < losses[0]
