"""MNIST classifier convergence (the reference's first example config;
≙ reference predict_test accuracy>=0.5, tests/utils.py:256-272)."""

import pytest

from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models.mnist import MNISTClassifier, MNISTDataModule
from ray_lightning_tpu.parallel.strategies import LocalStrategy


@pytest.mark.slow  # tier-1 diet (round 11): see pytest.ini 'slow'
def test_mnist_converges(tmp_path):
    trainer = Trainer(
        strategy=LocalStrategy(),
        max_epochs=2,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
    )
    trainer.fit(MNISTClassifier(), MNISTDataModule())
    assert trainer.callback_metrics["ptl/val_accuracy"] >= 0.5
