"""ResNet-18 for CIFAR-scale images (the reference's third example
config: ResNet-18 / CIFAR-10 over a multi-host data-parallel mesh).

The reference framework has no vision model of its own — its examples lean
on torchvision/pl_bolts (reference ``examples/ray_ddp_example.py``,
``ray_ddp_sharded_example.py:62``); this module provides the in-framework
equivalent so the reference's example grid is runnable end to end.

TPU-first design choices (not a torch translation):

* **NHWC layout** — XLA:TPU's native convolution layout; channels-last
  keeps the MXU fed without transposes.
* **GroupNorm instead of BatchNorm** — BatchNorm's running statistics need
  a mutable-state side channel and a cross-replica ``psum`` of batch
  moments every step; GroupNorm is stateless, batch-independent (so DP
  sharding never changes the math), and fuses into the surrounding
  elementwise ops.  This is the standard JAX/TPU substitution.
* **bf16-friendly** — parameters stay f32; the trainer's precision policy
  casts activations, and convs/matmuls land on the MXU in bf16.
* **Data parallel first** — conv channel counts are small (≤512), so
  ``param_partition_specs`` only annotates the classifier head for TP; the
  interesting axes for this model are data/fsdp (ZeRO), composed by
  ``parallel/sharding.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.core.data import ArrayDataset, NumpyLoader, TpuDataModule
from ray_lightning_tpu.core.module import TpuModule

__all__ = ["ResNet", "CIFARDataModule"]


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    scale = float(np.sqrt(2.0 / fan_in))
    return jax.random.normal(key, (kh, kw, cin, cout)) * scale


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x, w,
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _group_norm(x, g, b, groups=8, eps=1e-5):
    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, groups, c // groups)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    return xg.reshape(n, h, w, c) * g + b


class ResNet(TpuModule):
    """CIFAR-variant ResNet: 3×3 stem, 4 stages × ``depths`` basic blocks.

    ``ResNet()`` is ResNet-18 shaped (2-2-2-2 basic blocks, 64→512
    channels, ~11M params).
    """

    def __init__(
        self,
        depths: Sequence[int] = (2, 2, 2, 2),
        widths: Sequence[int] = (64, 128, 256, 512),
        num_classes: int = 10,
        lr: float = 1e-3,
        weight_decay: float = 5e-4,
        norm_groups: int = 8,
    ):
        super().__init__()
        self.save_hyperparameters(
            depths=tuple(depths), widths=tuple(widths),
            num_classes=num_classes, lr=lr, weight_decay=weight_decay,
            norm_groups=norm_groups,
        )

    # -- parameters ---------------------------------------------------
    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        h = self.hparams
        depths, widths = h["depths"], h["widths"]
        keys = iter(jax.random.split(rng, 4 + 4 * sum(depths) + 1))

        def norm(c):
            return {"g": jnp.ones((c,)), "b": jnp.zeros((c,))}

        params: Dict[str, Any] = {
            "stem": {"w": _conv_init(next(keys), 3, 3, 3, widths[0]),
                     "norm": norm(widths[0])},
        }
        cin = widths[0]
        for si, (d, cout) in enumerate(zip(depths, widths)):
            stage = []
            for bi in range(d):
                stride = 2 if (si > 0 and bi == 0) else 1
                block = {
                    "conv1": {"w": _conv_init(next(keys), 3, 3, cin, cout)},
                    "norm1": norm(cout),
                    "conv2": {"w": _conv_init(next(keys), 3, 3, cout, cout)},
                    "norm2": norm(cout),
                }
                if stride != 1 or cin != cout:
                    block["down"] = {
                        "w": _conv_init(next(keys), 1, 1, cin, cout),
                        "norm": norm(cout),
                    }
                stage.append(block)
                cin = cout
            params[f"stage{si}"] = stage
        fan_in = widths[-1]
        params["head"] = {
            "w": jax.random.normal(next(keys), (fan_in, h["num_classes"]))
            * float(np.sqrt(1.0 / fan_in)),
            "b": jnp.zeros((h["num_classes"],)),
        }
        return params

    def param_partition_specs(self) -> Dict[str, Any]:
        """TP annotations: only the classifier head is worth sharding at
        these widths; conv stacks stay replicated on the tensor axis (data
        and fsdp axes are layered on by the strategy)."""
        h = self.hparams

        def norm_spec():
            return {"g": P(), "b": P()}

        specs: Dict[str, Any] = {
            "stem": {"w": P(), "norm": norm_spec()},
            "head": {"w": P(None, "tensor"), "b": P("tensor")},
        }
        cin = h["widths"][0]
        for si, (d, cout) in enumerate(zip(h["depths"], h["widths"])):
            stage = []
            for bi in range(d):
                stride = 2 if (si > 0 and bi == 0) else 1
                block = {
                    "conv1": {"w": P()}, "norm1": norm_spec(),
                    "conv2": {"w": P()}, "norm2": norm_spec(),
                }
                if stride != 1 or cin != cout:
                    block["down"] = {"w": P(), "norm": norm_spec()}
                stage.append(block)
                cin = cout
            specs[f"stage{si}"] = stage
        return specs

    # -- forward ------------------------------------------------------
    def _block(self, p, x, stride, groups):
        out = _conv(x, p["conv1"]["w"], stride)
        out = _group_norm(out, p["norm1"]["g"], p["norm1"]["b"], groups)
        out = jax.nn.relu(out)
        out = _conv(out, p["conv2"]["w"], 1)
        out = _group_norm(out, p["norm2"]["g"], p["norm2"]["b"], groups)
        if "down" in p:
            x = _conv(x, p["down"]["w"], stride)
            x = _group_norm(x, p["down"]["norm"]["g"],
                            p["down"]["norm"]["b"], groups)
        return jax.nn.relu(out + x)

    def forward(self, params, x):
        h = self.hparams
        groups = h["norm_groups"]
        compute_dtype = (
            jnp.bfloat16 if getattr(self, "precision", "f32") == "bf16"
            else jnp.float32
        )
        x = x.astype(compute_dtype)
        cast = lambda t: jax.tree.map(  # noqa: E731
            lambda a: a.astype(compute_dtype), t)

        p = cast(params)
        x = _conv(x, p["stem"]["w"], 1)
        x = _group_norm(x, p["stem"]["norm"]["g"], p["stem"]["norm"]["b"],
                        groups)
        x = jax.nn.relu(x)
        for si in range(len(h["depths"])):
            for bi, block in enumerate(p[f"stage{si}"]):
                stride = 2 if (si > 0 and bi == 0) else 1
                x = self._block(block, x, stride, groups)
        x = x.mean(axis=(1, 2))  # global average pool
        logits = x @ p["head"]["w"] + p["head"]["b"]
        return logits.astype(jnp.float32)

    # -- steps --------------------------------------------------------
    def _loss_acc(self, params, batch):
        logits = self.forward(params, batch["x"])
        labels = batch["y"]
        loss = jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        )
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return loss, acc

    def training_step(self, params, batch, rng):
        loss, acc = self._loss_acc(params, batch)
        return loss, {"train_loss": loss, "train_accuracy": acc}

    def validation_step(self, params, batch):
        loss, acc = self._loss_acc(params, batch)
        return {"val_loss": loss, "val_accuracy": acc}

    def predict_step(self, params, batch):
        return jnp.argmax(self.forward(params, batch["x"]), axis=-1)

    def configure_optimizers(self):
        h = self.hparams
        return optax.chain(
            optax.add_decayed_weights(
                h["weight_decay"],
                mask=lambda params: jax.tree.map(
                    lambda a: a.ndim > 1, params),
            ),
            optax.adam(h["lr"]),
        )


class CIFARDataModule(TpuDataModule):
    """CIFAR-10-shaped data: real CIFAR if an npz is pointed at via
    ``data_path``, otherwise deterministic class-conditional synthetic
    images (zero-egress environments)."""

    def __init__(self, batch_size: int = 128, num_samples: int = 2048,
                 image_size: int = 32, num_classes: int = 10, seed: int = 0,
                 data_path: str | None = None):
        super().__init__()
        self.batch_size = batch_size
        self.num_samples = num_samples
        self.image_size = image_size
        self.num_classes = num_classes
        self.seed = seed
        self.data_path = data_path
        self._train: ArrayDataset | None = None
        self._val: ArrayDataset | None = None

    def _synthetic(self):
        rng = np.random.default_rng(self.seed)
        n, s = self.num_samples, self.image_size
        labels = rng.integers(0, self.num_classes, n).astype(np.int32)
        base = rng.standard_normal(
            (self.num_classes, s, s, 3), dtype=np.float32)
        imgs = base[labels] + 0.7 * rng.standard_normal(
            (n, s, s, 3), dtype=np.float32)
        return imgs, labels

    def setup(self, stage: str) -> None:
        if self._train is not None:
            return
        if self.data_path:
            blob = np.load(self.data_path)
            imgs = blob["x"].astype(np.float32)
            if imgs.ndim == 4 and imgs.shape[1] == 3:  # NCHW → NHWC
                imgs = imgs.transpose(0, 2, 3, 1)
            if imgs.max() > 2.0:
                imgs = imgs / 255.0
            labels = blob["y"].astype(np.int32)
        else:
            imgs, labels = self._synthetic()
        n_val = max(self.batch_size, len(imgs) // 10)
        self._val = ArrayDataset(x=imgs[:n_val], y=labels[:n_val])
        self._train = ArrayDataset(x=imgs[n_val:], y=labels[n_val:])

    def train_dataloader(self):
        return NumpyLoader(
            self._train, batch_size=self.batch_size, shuffle=True,
            seed=self.seed, shard_index=self.shard_index,
            num_shards=self.num_shards,
        )

    def val_dataloader(self):
        return NumpyLoader(
            self._val, batch_size=self.batch_size,
            shard_index=self.shard_index, num_shards=self.num_shards,
        )

    def test_dataloader(self):
        return self.val_dataloader()

    def predict_dataloader(self):
        return self.val_dataloader()
