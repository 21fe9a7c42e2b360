"""Orbax interop bridges: ecosystem-format export/import round trips."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@pytest.fixture(scope="module", autouse=True)
def _orbax():
    """``orbax.checkpoint`` (5 s of import) comes in where these cases
    run, not where every xdist worker collects them."""
    global load_orbax, save_orbax
    pytest.importorskip("orbax.checkpoint",
                        reason="orbax-checkpoint not installed")
    from ray_lightning_tpu.utils.orbax_io import load_orbax, save_orbax


def _tree():
    return {
        "params": {
            "w": jnp.arange(12.0, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((4,), jnp.bfloat16),
        },
        "step": jnp.int32(7),
    }


def test_round_trip(tmp_path):
    tree = _tree()
    p = save_orbax(str(tmp_path / "ckpt"), tree)
    back = load_orbax(p)
    flat_a = jax.tree_util.tree_leaves_with_path(tree)
    flat_b = jax.tree_util.tree_leaves_with_path(back)
    assert [k for k, _ in flat_a] == [k for k, _ in flat_b]
    for (_, a), (_, b) in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).dtype == np.asarray(b).dtype


def test_restore_onto_mesh_shardings(tmp_path):
    """A checkpoint written unsharded restores directly onto a 2x4 mesh
    with NamedShardings — the cross-topology property."""
    tree = {"w": jnp.arange(64.0, dtype=jnp.float32).reshape(8, 8)}
    p = save_orbax(str(tmp_path / "ckpt"), tree)

    mesh = Mesh(mesh_utils.create_device_mesh((2, 4)), ("data", "tensor"))
    sh = NamedSharding(mesh, P("data", "tensor"))
    target = {
        "w": jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=sh)
    }
    back = load_orbax(p, target=target)
    assert back["w"].sharding == sh
    np.testing.assert_array_equal(
        np.asarray(back["w"]), np.asarray(tree["w"])
    )


def test_trained_state_round_trips(tmp_path):
    """Export a real trained TrainState's pytree and re-import it."""
    from ray_lightning_tpu.core.module import TrainState
    from ray_lightning_tpu.models.boring import BoringModel

    m = BoringModel()
    params = m.init_params(jax.random.PRNGKey(0))
    state = TrainState.create(params, m.configure_optimizers())
    tree = {"params": state.params, "opt_state": state.opt_state,
            "step": state.step}
    p = save_orbax(str(tmp_path / "state"), tree)
    back = load_orbax(p)
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(tree),
        jax.tree_util.tree_leaves_with_path(back),
    ):
        assert ka == kb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_overwrite_guard(tmp_path):
    tree = _tree()
    p = save_orbax(str(tmp_path / "c"), tree)
    with pytest.raises(Exception):
        save_orbax(p, tree)  # no overwrite without force
    save_orbax(p, tree, overwrite=True)
