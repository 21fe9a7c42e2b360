"""Quantized, bucketed gradient sync (parallel/grad_sync.py +
ops/collective_quant.py): bucket-plan edge cases, codec error bounds, the
compressed all-reduce against an exact psum, wire accounting, and
fit-level loss parity (full vs int8 vs int8+error-feedback) on the
8-device CPU mesh.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.ops import collective_quant as cq
from ray_lightning_tpu.parallel import grad_sync as gsync
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.strategies import LocalStrategy

from test_trainer_features import FixedDataModule


# -- bucket plan -------------------------------------------------------------

def _abstract(*shapes):
    return [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]


def test_bucket_plan_covers_every_leaf_once_in_order():
    tree = _abstract((64, 64), (64,), (128, 32), (32,))
    plan = gsync.build_bucket_plan(
        tree, n_shards=8, bucket_bytes=4 * (64 * 64 + 64), block_size=64
    )
    seen = [i for b in plan.buckets for i in b.indices]
    assert seen == [0, 1, 2, 3]  # layer order, each leaf exactly once
    sizes = [s for b in plan.buckets for s in b.sizes]
    assert sizes == [64 * 64, 64, 128 * 32, 32]
    assert plan.total_elems == sum(sizes)
    # Buckets respect the byte bound: first bucket is exactly the two
    # leaves that fit, the rest spill over.
    assert plan.buckets[0].indices == (0, 1)


def test_bucket_plan_empty_tree():
    plan = gsync.build_bucket_plan([], n_shards=8)
    assert plan.num_buckets == 0
    assert plan.total_elems == 0
    assert plan.wire_bytes_per_step("int8") == 0


def test_bucket_plan_skips_zero_element_leaves():
    # An empty placeholder leaf has nothing to sync; counting it as one
    # phantom element would desync padding from the actual payload.
    tree = _abstract((4, 4), (0,), ())
    plan = gsync.build_bucket_plan(tree, n_shards=2, block_size=8)
    sizes = [s for b in plan.buckets for s in b.sizes]
    assert sizes == [16, 1]  # matrix + scalar; the (0,) leaf is skipped
    assert 1 not in [i for b in plan.buckets for i in b.indices]


def test_env_bus_forwarded_to_worker_env():
    import os

    from ray_lightning_tpu.parallel.strategies import RayStrategy

    os.environ["RLT_GRAD_COMM"] = "int8_ef"
    try:
        s = RayStrategy(num_workers=1)
        # The env bus rides env_per_worker like the compile-cache dir, so
        # remote workers (agent/Ray spawned — they inherit the AGENT's
        # env, not the driver's) still see the driver's request.
        assert s.env_per_worker["RLT_GRAD_COMM"] == "int8_ef"
    finally:
        del os.environ["RLT_GRAD_COMM"]


def test_bucket_plan_single_tiny_param_pads_to_alignment():
    plan = gsync.build_bucket_plan(
        _abstract((3,)), n_shards=8, block_size=16
    )
    (b,) = plan.buckets
    assert b.size == 3
    assert b.padded == 128  # one n_shards*block_size alignment unit
    assert b.padded % (8 * 16) == 0


def test_bucket_plan_oversized_leaf_gets_own_bucket():
    # leaf 1 alone exceeds the bound; it must not merge with neighbors.
    tree = _abstract((8,), (4096,), (8,))
    plan = gsync.build_bucket_plan(
        tree, n_shards=2, bucket_bytes=1024, block_size=8
    )
    assert [b.indices for b in plan.buckets] == [(0,), (1,), (2,)]
    # Ragged tail: the last bucket holds only the 8-element leaf.
    assert plan.buckets[-1].size == 8


def test_wire_accounting_ratio_beats_3_5x():
    plan = gsync.build_bucket_plan(
        _abstract((256, 128), (128,)), n_shards=8, block_size=256
    )
    full = plan.wire_bytes_per_step("full")
    q = plan.wire_bytes_per_step("int8")
    assert full / q >= 3.5
    # int8 payload + f32 scales, ring-accounted: 2(n-1)/n traversals.
    padded = sum(b.padded for b in plan.buckets)
    expect = int(2 * 7 / 8 * (padded + padded // 256 * 4))
    assert q == expect


# -- block-scaled codec ------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal(1024).astype(np.float32) * 3.0)
    q, s = cq.quantize_block_scaled(v, 128)
    back = cq.dequantize_block_scaled(q, s, 128)
    # Per-block bound: |err| <= scale/2 = absmax/254.
    err = np.abs(np.asarray(v - back)).reshape(-1, 128)
    amax = np.abs(np.asarray(v)).reshape(-1, 128).max(axis=1)
    assert (err.max(axis=1) <= amax / 254.0 + 1e-7).all()


def test_quantize_zero_block_is_exact_and_finite():
    v = jnp.zeros((256,), jnp.float32)
    q, s = cq.quantize_block_scaled(v, 128)
    assert np.asarray(q).sum() == 0
    assert np.isfinite(np.asarray(s)).all()
    back = cq.dequantize_block_scaled(q, s, 128)
    assert np.asarray(back).sum() == 0


# -- compressed all-reduce vs exact psum ------------------------------------

@pytest.fixture
def mesh8(cpu_mesh_devices):
    return build_mesh(MeshSpec({"data": 8}))


def _per_device_partials(n, size, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, size)).astype(np.float32)


def test_int8_all_reduce_matches_psum_within_quant_error(mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    size, block = 8 * 256, 64
    parts = _per_device_partials(8, size)

    def body(x):
        red, err = cq.int8_all_reduce(
            x[0], ("data",), 8, block, want_error=True
        )
        return red[None], err[None]

    fn = jax.shard_map(
        body, mesh=mesh8, in_specs=(P("data"),),
        out_specs=(P("data"), P("data")), check_vma=False,
    )
    red, err = jax.jit(fn)(
        jax.device_put(parts, NamedSharding(mesh8, P("data")))
    )
    red, err = np.asarray(red), np.asarray(err)
    exact = parts.sum(axis=0)
    # Every device holds the same reduced vector...
    assert np.allclose(red, red[0][None], atol=0)
    # ...close to the exact sum (two quantization passes of error).
    scale = np.abs(parts).max() / 127.0
    assert np.abs(red[0] - exact).max() <= (8 + 1) * scale
    # EF invariant: the per-device errors SUM to exactly the total
    # compression error, so reinjection telescopes.
    np.testing.assert_allclose(
        err.sum(axis=0), exact - red[0], rtol=1e-5, atol=1e-5
    )


# -- resolution / gating -----------------------------------------------------

def test_resolution_downgrades_loudly(mesh8):
    module = BoringModel(in_dim=64, out_dim=8)
    cfg = {"mode": "int8", "dcn_only": False}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert gsync.maybe_build_grad_sync(
            module, mesh8, cfg, mode="shard_map") is None
        assert gsync.maybe_build_grad_sync(
            module, mesh8, cfg, mode="gspmd", zero_stage=3) is None
    assert len(w) == 2 and all("full width" in str(x.message) for x in w)
    # dcn_only=True on a single-process mesh: ICI-only, stays full.
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert gsync.maybe_build_grad_sync(
            module, mesh8, "int8", mode="gspmd") is None
    assert any("ICI-only" in str(x.message) for x in w)
    # full mode: silently inactive (the default path).
    assert gsync.maybe_build_grad_sync(module, mesh8, "full") is None
    assert gsync.maybe_build_grad_sync(module, mesh8, None) is None


def test_resolution_rejects_model_parallel_mesh(cpu_mesh_devices):
    mesh = build_mesh(MeshSpec({"data": 4, "tensor": 2}))
    module = BoringModel(in_dim=64, out_dim=8)
    with pytest.warns(UserWarning, match="model-parallel"):
        assert gsync.maybe_build_grad_sync(
            module, mesh, {"mode": "int8", "dcn_only": False}) is None


def test_bad_mode_fails_fast():
    with pytest.raises(ValueError, match="grad_comm mode"):
        gsync.GradCommConfig(mode="int4")
    with pytest.raises(ValueError, match="grad_comm mode"):
        LocalStrategy(grad_comm="int4")


def test_env_bus_sets_default(monkeypatch):
    monkeypatch.setenv("RLT_GRAD_COMM", "int8_ef")
    monkeypatch.setenv("RLT_GRAD_BUCKET_MB", "2")
    monkeypatch.setenv("RLT_GRAD_DCN_ONLY", "0")
    cfg = gsync.GradCommConfig.coerce(None)
    assert cfg.mode == "int8_ef"
    assert cfg.bucket_bytes == 2 * 2**20
    assert cfg.dcn_only is False


# -- fit-level parity on the 8-device CPU mesh -------------------------------

def _fit(tmp_path, grad_comm, max_epochs=2, in_dim=256, out_dim=128):
    x = np.random.default_rng(7).standard_normal(
        (64, in_dim)).astype(np.float32)
    module = BoringModel(in_dim=in_dim, out_dim=out_dim, lr=0.05)
    trainer = Trainer(
        strategy=LocalStrategy(
            mesh_axes={"data": 8}, grad_comm=grad_comm
        ),
        max_epochs=max_epochs,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        log_every_n_steps=1,
    )
    trainer.fit(module, FixedDataModule(x, batch_size=16))
    return trainer


def test_fit_loss_parity_int8_and_ef_vs_full(tmp_path):
    t_full = _fit(tmp_path / "full", "full")
    t_ef = _fit(
        tmp_path / "ef", {"mode": "int8_ef", "dcn_only": False}
    )
    # Small bucket bound forces the multi-bucket path: the 256x128
    # weight exceeds it (own bucket), the bias trails in a ragged one.
    t_i8 = _fit(
        tmp_path / "i8",
        {"mode": "int8", "dcn_only": False, "bucket_bytes": 65536},
    )
    ref = t_full.callback_metrics["train_loss"]
    # Error feedback: within 1% relative of full-width final loss.
    assert abs(t_ef.callback_metrics["train_loss"] - ref) <= 0.01 * abs(ref)
    # Plain int8: bounded divergence (no residual, bias may accumulate).
    assert abs(t_i8.callback_metrics["train_loss"] - ref) <= 0.10 * abs(ref)

    # Wire accounting is a recorded artifact on both surfaces:
    for t, mode in ((t_ef, "int8_ef"), (t_i8, "int8")):
        assert t.comm_stats["grad_sync_mode"] == mode
        assert t.comm_stats["grad_sync_compression_ratio"] >= 3.5
        assert (
            t.callback_metrics["grad_sync_bytes"]
            == t.comm_stats["grad_sync_bytes"]
        )
        assert t.comm_stats["grad_sync_bytes"] * 3.5 <= (
            t.comm_stats["grad_sync_bytes_full_width"]
        )
    assert t_full.comm_stats == {"grad_sync_mode": "full"}
    assert "grad_sync_bytes" not in t_full.callback_metrics
    # The bounded-bucket run really synced in two collective groups.
    assert t_i8.comm_stats["grad_sync_buckets"] == 2
    assert t_ef.comm_stats["grad_sync_buckets"] == 1

    # The EF residual rides the DEVICE-side train state only: gathered
    # payloads (checkpoints, the rank-0→driver stream) exclude it — it
    # is n_devices × params of f32, and resumes re-attach zeros.
    assert t_ef.state.grad_residual is None
    assert t_full.state.grad_residual is None


def test_ef_checkpoint_roundtrip_and_mode_switch(tmp_path):
    x = np.random.default_rng(3).standard_normal((32, 64)).astype(
        np.float32)
    dm = FixedDataModule(x, batch_size=16)
    ef = {"mode": "int8_ef", "dcn_only": False}

    def make_trainer(grad_comm, resume=None):
        return Trainer(
            strategy=LocalStrategy(
                mesh_axes={"data": 8}, grad_comm=grad_comm
            ),
            max_epochs=2 if resume else 1,
            default_root_dir=str(tmp_path),
            enable_checkpointing=False,
            resume_from_checkpoint=resume,
        )

    t1 = make_trainer(ef)
    t1.fit(BoringModel(in_dim=64, out_dim=32, lr=0.05), dm)
    ckpt = str(tmp_path / "ef.ckpt")
    t1.save_checkpoint(ckpt)
    assert t1.comm_stats["grad_sync_mode"] == "int8_ef"

    # EF → EF resume: the checkpoint carries no residual (gathers
    # exclude it); a fresh zero one is attached and training proceeds.
    t2 = make_trainer(ef, resume=ckpt)
    t2.fit(BoringModel(in_dim=64, out_dim=32, lr=0.05), dm)
    assert t2.comm_stats["grad_sync_mode"] == "int8_ef"
    assert t2.global_step > t1.global_step

    # EF → full resume: no residual expected anywhere, loads cleanly.
    t3 = make_trainer("full", resume=ckpt)
    t3.fit(BoringModel(in_dim=64, out_dim=32, lr=0.05), dm)
    assert t3.comm_stats == {"grad_sync_mode": "full"}

    # full → EF resume: a fresh zero residual is attached on-device.
    plain = str(tmp_path / "plain.ckpt")
    t3.save_checkpoint(plain)
    t4 = make_trainer(ef, resume=plain)
    t4.fit(BoringModel(in_dim=64, out_dim=32, lr=0.05), dm)
    assert t4.comm_stats["grad_sync_mode"] == "int8_ef"
