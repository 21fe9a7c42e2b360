"""Multi-worker (multi-process mesh) integration tests.

The distributed heart: 2 worker actors each owning 8 virtual CPU devices
join ONE 16-device mesh via jax.distributed (Gloo collectives standing in
for ICI/DCN).  ≙ the reference's simulated-cluster tier
(``ray.cluster_utils.Cluster``, ``test_ddp.py:54-61``) — real multi-process
collectives without real hardware.
"""

import numpy as np
import pytest

import jax

from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models import (
    BoringDataModule,
    BoringModel,
    XORDataModule,
    XORModel,
)
from ray_lightning_tpu.parallel.strategies import (
    LocalStrategy,
    RayShardedStrategy,
    RayStrategy,
)

from utils import get_trainer

pytestmark = [pytest.mark.remote, pytest.mark.multiworker]


def test_two_worker_fit_matches_local(tmp_path):
    """2-process/16-device mesh reproduces the single-process trajectory."""
    dm = lambda: BoringDataModule(length=64, batch_size=32)  # noqa: E731
    local = get_trainer(LocalStrategy(), max_epochs=2, tmp_path=tmp_path / "a")
    local.fit(BoringModel(), dm())

    remote = get_trainer(
        RayStrategy(num_workers=2), max_epochs=2, tmp_path=tmp_path / "b"
    )
    remote.fit(BoringModel(), dm())
    assert remote.params is not None
    for x, y in zip(
        jax.tree_util.tree_leaves(local.params),
        jax.tree_util.tree_leaves(remote.params),
    ):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=5e-3, atol=1e-3
        )
    assert "val_loss" in remote.callback_metrics


def test_two_worker_zero3_sharded(tmp_path):
    """ZeRO-3 params sharded across a 2-process mesh; checkpoint is
    topology-independent (gathered), loadable on the driver."""
    trainer = get_trainer(
        RayShardedStrategy(num_workers=2, zero_stage=3),
        max_epochs=1,
        tmp_path=tmp_path,
    )
    trainer.fit(
        BoringModel(in_dim=256, out_dim=128),
        BoringDataModule(length=64, batch_size=32, in_dim=256),
    )
    assert trainer.params["w"].shape == (256, 128)  # gathered, full shape
    assert np.isfinite(trainer.callback_metrics["train_loss"])


def test_two_worker_predict_row_order(tmp_path):
    """Predictions must come back in dataset row order despite host-
    contiguous batch splitting (the interleave-reassembly contract)."""
    trainer = get_trainer(
        RayStrategy(num_workers=2), max_epochs=6, tmp_path=tmp_path
    )
    trainer.fit(XORModel(), XORDataModule(batch_size=16))
    preds = trainer.predict(XORModel(), XORDataModule(batch_size=16))
    # XOR table tiles [0,1,1,0]; a correctly ordered, converged model
    # reproduces the tiling exactly.
    expected = np.tile([0, 1, 1, 0], len(preds) // 4)
    assert (preds == expected).mean() > 0.9


def test_zero3_restart_checkpoint_sharded_per_host(tmp_path):
    """VERDICT r3 item #3 'Done' criterion: a ZeRO-3 multiworker restart
    checkpoint never materializes the full state on one host — each of
    the 2 processes writes only its addressable shards, and the set
    reassembles to the full shapes."""
    from ray_lightning_tpu.utils.sharded_ckpt import (
        _read_shard_header, is_sharded_ckpt, load_sharded,
    )

    rs = tmp_path / "restarts"
    trainer = get_trainer(
        RayShardedStrategy(num_workers=2, zero_stage=3),
        max_epochs=1, tmp_path=tmp_path, restart_dir=str(rs),
    )
    trainer.fit(
        BoringModel(in_dim=256, out_dim=128),
        BoringDataModule(length=64, batch_size=32, in_dim=256),
    )
    tags = [p for p in rs.iterdir() if p.name.endswith(".ckpt")]
    assert len(tags) == 1 and is_sharded_ckpt(str(tags[0]))
    # One file per process (each beside its ``.crc32`` sidecar), not one
    # gathered blob.
    shards = sorted(tags[0].glob("shard-*.ckpt"))
    assert len(shards) == 2
    sizes = [s.stat().st_size for s in shards]
    # ZeRO-3: each host holds ~half the (w, m, v) state; neither file
    # may contain the whole thing.
    assert max(sizes) < 0.75 * sum(sizes), sizes
    # No process wrote the sharded weight whole: each file holds the rows
    # its own devices own, and the two sets are disjoint and complete.
    rows = []
    for shard in shards:
        header, _ = _read_shard_header(str(shard))
        (w,) = [leaf for leaf in header["leaves"] if leaf["s"] == [256, 128]]
        rows.append({r for e in w["e"] for r in range(*e["i"][0])})
    assert len(rows[0]) == len(rows[1]) == 128
    assert rows[0] | rows[1] == set(range(256))
    payload = load_sharded(str(tags[0]))
    state = payload["state"]
    assert np.asarray(
        jax.tree_util.tree_leaves(state.params)[0]
    ).shape in ((256, 128), (128,))
