"""Backward-overlapped gradient sync (parallel/overlap.py) + quantized
MPMD wire (mpmd/transfer.py WireCodec): overlap-plan/partition units,
fit-level loss parity of the overlapped schedule against step-end sync
(bitwise at full width, 1%-relative at int8_ef) across accumulation /
megastep / ZeRO flavors, EF-residual reconciliation across a segment-
count change, wire-dtype parity + compression ratio on the in-process
2-worker pipeline, and the chaos contract on quantized SEND segments.
"""

import dataclasses
import warnings

import numpy as np
import pytest

import jax

from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.models import BoringModel
from ray_lightning_tpu.models.gpt import GPT, GPTConfig, SyntheticLMDataModule
from ray_lightning_tpu.parallel import grad_sync as gsync
from ray_lightning_tpu.parallel import overlap as ovl
from ray_lightning_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_lightning_tpu.parallel.strategies import LocalStrategy


# -- knob normalization / resolution -----------------------------------------

def test_normalize_grad_overlap_values():
    assert ovl.normalize_grad_overlap(None) is None
    assert ovl.normalize_grad_overlap("") == 0
    assert ovl.normalize_grad_overlap("off") == 0
    assert ovl.normalize_grad_overlap("4") == 4
    assert ovl.normalize_grad_overlap(2) == 2
    with pytest.raises(ValueError, match="expected 'off'"):
        ovl.normalize_grad_overlap("bogus")
    with pytest.raises(ValueError, match=">= 0"):
        ovl.normalize_grad_overlap(-1)
    with pytest.raises(TypeError):
        ovl.normalize_grad_overlap(True)


def test_resolve_grad_overlap_env_bus(monkeypatch):
    monkeypatch.delenv("RLT_GRAD_OVERLAP", raising=False)
    assert ovl.resolve_grad_overlap(None) == 0
    monkeypatch.setenv("RLT_GRAD_OVERLAP", "3")
    assert ovl.resolve_grad_overlap(None) == 3
    # Explicit knob wins over the bus; an explicit "" clears it.
    assert ovl.resolve_grad_overlap(2) == 2
    assert ovl.resolve_grad_overlap("") == 0
    monkeypatch.setenv("RLT_GRAD_OVERLAP", "")
    assert ovl.resolve_grad_overlap(None) == 0


# -- overlap plan units ------------------------------------------------------

def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, np.float32)


def _gpt3():
    # Three layers: segments=2 splits non-divisibly (2+1).
    return dataclasses.replace(GPTConfig.tiny(), n_layer=3)


def _gpt_plan(segments, n_shards=8, **kw):
    module = GPT(_gpt3())
    abstract = jax.eval_shape(module.init_params, jax.random.PRNGKey(0))
    spec = module.grad_overlap_groups(abstract, segments)
    return ovl.build_overlap_plan(spec, n_shards, **kw), abstract


def test_overlap_plan_partitions_gpt_exactly():
    plan, abstract = _gpt_plan(segments=2)
    mono = gsync.build_bucket_plan(abstract, n_shards=8)
    # The groups partition the whole tree — same element count as the
    # monolithic step-end plan, no leaf lost or double-counted.
    assert plan.total_elems == mono.total_elems
    # Backward-completion order: head first, trunk segments, embeddings
    # last; segments=2 over 3 layers splits non-divisibly.
    assert [g.name for g in plan.groups] == ["head", "seg0", "seg1", "embed"]
    assert plan.trunk_segments == 2
    seg_layers = [g.leaf_sizes for g in plan.groups if not g.entry]
    n_per_layer = sum(plan.group("seg0").leaf_sizes) // 2
    assert sum(plan.group("seg1").leaf_sizes) == n_per_layer
    assert len(seg_layers) == 2
    # Residual slices are contiguous and disjoint in group order.
    offset = 0
    for g in plan.groups:
        assert g.resid_offset == offset
        offset += g.plan.total_padded
    assert plan.total_padded == offset
    # Entry groups carry their top-level keys; trunk segments don't.
    assert set(plan.group("head").keys) == {"ln_f_g", "ln_f_b"}
    assert set(plan.group("embed").keys) == {"wte", "wpe"}
    assert plan.group("seg0").keys == ()


def test_overlap_plan_segments_clamp_to_layer_count():
    # More segments than layers: the module clamps to n_layer sub-scans.
    plan, _ = _gpt_plan(segments=16)
    assert plan.trunk_segments == 3


def test_overlap_plan_oversize_leaf_gets_own_bucket():
    # Within a group, a leaf exceeding bucket_bytes must not merge —
    # same packer rule as the step-end plan, applied per group.
    # Dict keys flatten alphabetically — a0/a1/a2 pins leaf order.
    spec = [
        ("a", {"a0": _sds(8), "a1": _sds(4096), "a2": _sds(8)}, True),
        ("b", {"x": _sds(64)}, True),
    ]
    plan = ovl.build_overlap_plan(
        spec, n_shards=2, bucket_bytes=1024, block_size=8
    )
    assert [b.indices for b in plan.group("a").plan.buckets] == [
        (0,), (1,), (2,)
    ]
    assert plan.num_buckets == 4
    # Accounting sums over groups like one plan.
    assert plan.wire_bytes_per_step("int8") == sum(
        g.plan.wire_bytes_per_step("int8") for g in plan.groups
    )


def test_overlap_plan_build_errors():
    with pytest.raises(ValueError, match="duplicate"):
        ovl.build_overlap_plan(
            [("g", {"w": _sds(8)}, True), ("g", {"x": _sds(8)}, True)],
            n_shards=2,
        )
    with pytest.raises(ValueError, match="must be a dict"):
        ovl.build_overlap_plan([("g", [_sds(8)], True)], n_shards=2)
    with pytest.raises(ValueError, match="no groups"):
        ovl.build_overlap_plan([], n_shards=2)


def test_tap_plane_guards_misrouted_forwards():
    plan = ovl.build_overlap_plan(
        [("g0", {"w": _sds(16)}, True), ("g1", {"v": _sds(16)}, True)],
        n_shards=2,
    )
    plane = ovl.TapPlane(plan, ("data",), 2, use_ef=False)
    with pytest.raises(ValueError, match="not in the overlap plan"):
        plane.tap("nope", {"w": np.zeros(16, np.float32)})
    # Layout drift between the declared group and the tapped subtree.
    with pytest.raises(ValueError, match="leaf layout"):
        plane.tap("g0", {"w": np.zeros(8, np.float32)})
    out = plane.tap("g0", {"w": np.zeros(16, np.float32)})
    assert out["w"].shape == (16,)
    with pytest.raises(ValueError, match="consumed twice"):
        plane.tap("g0", {"w": np.zeros(16, np.float32)})
    # g1 was declared but never tapped: a silent miss would drop its
    # sync, so the trace-end check must name it.
    with pytest.raises(ValueError, match="never tapped.*g1"):
        plane.check_consumed()


# -- resolution: loud downgrade + coverage failure ---------------------------

@pytest.fixture
def mesh8(cpu_mesh_devices):
    return build_mesh(MeshSpec({"data": 8}))


def test_overlap_without_groups_downgrades_loudly(mesh8):
    # BoringModel has no grad_overlap_groups: the sync stays active but
    # step-end — schedule changes are never silent.
    module = BoringModel(in_dim=64, out_dim=8)
    with pytest.warns(UserWarning, match="does not partition"):
        gs = gsync.maybe_build_grad_sync(
            module, mesh8, {"mode": "int8_ef", "dcn_only": False},
            overlap_segments=2,
        )
    assert gs is not None
    assert gs.overlap is None
    assert gs.stats()["grad_sync_overlap_segments"] == 0


def test_overlap_partition_coverage_enforced(mesh8):
    class LeakyGPT(GPT):
        def grad_overlap_groups(self, abstract_params, segments):
            groups = super().grad_overlap_groups(abstract_params, segments)
            return groups[:-1]  # drop the embed group: params uncovered

    module = LeakyGPT(_gpt3())
    with pytest.raises(ValueError, match="partition the whole param tree"):
        gsync.maybe_build_grad_sync(
            module, mesh8, {"mode": "int8_ef", "dcn_only": False},
            overlap_segments=2,
        )


def test_overlap_active_plan_is_the_overlap_plan(mesh8):
    module = GPT(_gpt3())
    gs = gsync.maybe_build_grad_sync(
        module, mesh8, {"mode": "int8_ef", "dcn_only": False},
        overlap_segments=2,
    )
    assert isinstance(gs.plan, ovl.OverlapPlan)
    assert gs.stats()["grad_sync_overlap_segments"] == 2
    # Wire accounting carries over: bytes come from the same codec and
    # alignment rule, so the compression ratio still clears the bar.
    full = gs.plan.wire_bytes_per_step("full")
    assert full / gs.plan.wire_bytes_per_step("int8") >= 3.5


def test_reconcile_residual_across_segment_change(mesh8):
    from ray_lightning_tpu.core.module import TrainState

    # Six layers: a 3+3 split pads each half-trunk group separately,
    # while one 6-layer group crosses an extra alignment boundary — the
    # two layouts land on different residual-row lengths (smaller layer
    # counts can coincide, which is exactly the silent case to avoid).
    module = GPT(dataclasses.replace(GPTConfig.tiny(), n_layer=6))

    def build(segments):
        return gsync.maybe_build_grad_sync(
            module, mesh8, {"mode": "int8_ef", "dcn_only": False},
            overlap_segments=segments,
        )

    g1, g2 = build(1), build(2)
    # The group layouts pad differently, so the residual rows disagree.
    assert g1.plan.total_padded != g2.plan.total_padded
    stale = TrainState(
        {}, None, 0, np.ones((8, g1.plan.total_padded), np.float32)
    )
    with pytest.warns(UserWarning, match="resetting to zero"):
        out = g2.reconcile_resumed_state(stale)
    assert out.grad_residual.shape == (8, g2.plan.total_padded)
    assert not out.grad_residual.any()
    # A residual already in this run's layout passes through untouched.
    good = TrainState(
        {}, None, 0, np.ones((8, g2.plan.total_padded), np.float32)
    )
    assert g2.reconcile_resumed_state(good) is good


# -- fit-level parity: overlapped vs step-end --------------------------------

def _fit_gpt(tmp_path, *, grad_comm, segments, accumulate=1,
             megastep=None, zero_stage=0, num_batches=8,
             resume=None, max_epochs=1):
    cfg = GPTConfig.tiny()
    trainer = Trainer(
        strategy=LocalStrategy(
            mesh_axes={"data": 8},
            grad_comm=grad_comm,
            grad_overlap_segments=segments,
            megastep=megastep,
            zero_stage=zero_stage,
        ),
        max_epochs=max_epochs,
        accumulate_grad_batches=accumulate,
        default_root_dir=str(tmp_path),
        enable_checkpointing=False,
        resume_from_checkpoint=resume,
    )
    trainer.fit(
        GPT(cfg), SyntheticLMDataModule(cfg, batch_size=8,
                                        num_batches=num_batches)
    )
    return trainer


def test_full_width_segmentation_is_bitwise_neutral(tmp_path):
    """G sub-scans with no taps (grad_comm full) run the same per-layer
    op sequence as the single scan — segmentation alone must not move a
    single bit, so RLT_GRAD_OVERLAP is safe to flip independently."""
    t0 = _fit_gpt(tmp_path / "g0", grad_comm="full", segments=0)
    t2 = _fit_gpt(tmp_path / "g2", grad_comm="full", segments=2)
    assert (
        t0.callback_metrics["train_loss"]
        == t2.callback_metrics["train_loss"]
    )


def test_overlap_ef_parity_fast(tmp_path):
    """The headline contract on the minimal config: same wire bytes,
    same mode, loss within 1% relative of the step-end schedule."""
    ef = {"mode": "int8_ef", "dcn_only": False}
    t_end = _fit_gpt(tmp_path / "end", grad_comm=ef, segments=0)
    t_ovl = _fit_gpt(tmp_path / "ovl", grad_comm=ef, segments=2)
    ref = t_end.callback_metrics["train_loss"]
    assert abs(t_ovl.callback_metrics["train_loss"] - ref) <= 0.01 * abs(ref)
    # Overlap changes the SCHEDULE, not the wire: same codec and
    # alignment rule, so bytes agree up to per-group padding (at most
    # align-1 extra elements per group — well under 2%).
    b_end = t_end.comm_stats["grad_sync_bytes"]
    b_ovl = t_ovl.comm_stats["grad_sync_bytes"]
    assert abs(b_ovl - b_end) <= 0.02 * b_end
    assert t_end.comm_stats["grad_sync_overlap_segments"] == 0
    assert t_ovl.comm_stats["grad_sync_overlap_segments"] == 2
    assert t_ovl.comm_stats["grad_sync_mode"] == "int8_ef"


@pytest.mark.slow
@pytest.mark.parametrize("accumulate,megastep,zero_stage", [
    (4, None, 0),
    (1, 4, 0),
    (1, None, 1),
    (4, 4, 1),
])
def test_overlap_ef_parity_matrix(tmp_path, accumulate, megastep,
                                  zero_stage):
    """Overlapped sync composes with the loop's other schedules —
    accumulation (taps fire per micro-batch, the accumulator averages
    synced grads), megastep (taps live inside the scanned stride body)
    and ZeRO-1 (sharded optimizer consumes the same synced grads)."""
    ef = {"mode": "int8_ef", "dcn_only": False}
    kw = dict(
        accumulate=accumulate, megastep=megastep, zero_stage=zero_stage,
        num_batches=16,
    )
    t_end = _fit_gpt(tmp_path / "end", grad_comm=ef, segments=0, **kw)
    t_ovl = _fit_gpt(tmp_path / "ovl", grad_comm=ef, segments=2, **kw)
    ref = t_end.callback_metrics["train_loss"]
    assert abs(t_ovl.callback_metrics["train_loss"] - ref) <= 0.01 * abs(ref)
    b_end = t_end.comm_stats["grad_sync_bytes"]
    b_ovl = t_ovl.comm_stats["grad_sync_bytes"]
    assert abs(b_ovl - b_end) <= 0.02 * b_end
    assert t_ovl.global_step == t_end.global_step


@pytest.mark.slow
def test_overlap_resume_across_segment_count_change(tmp_path):
    """A checkpoint from a G=2 EF fit resumes into a G=1 fit: gathers
    exclude the per-device residual, so the new layout attaches a fresh
    zero row and training proceeds on the new schedule."""
    ef = {"mode": "int8_ef", "dcn_only": False}
    t1 = _fit_gpt(tmp_path, grad_comm=ef, segments=2)
    ckpt = str(tmp_path / "g2.ckpt")
    t1.save_checkpoint(ckpt)
    t2 = _fit_gpt(
        tmp_path, grad_comm=ef, segments=1, resume=ckpt, max_epochs=2
    )
    assert t2.comm_stats["grad_sync_overlap_segments"] == 1
    assert t2.global_step > t1.global_step
    assert np.isfinite(t2.callback_metrics["train_loss"])


# -- MPMD quantized wire -----------------------------------------------------

def test_wire_dtype_config_coerce(monkeypatch):
    from ray_lightning_tpu.mpmd.transfer import WireDtypeConfig

    monkeypatch.delenv("RLT_MPMD_WIRE_DTYPE", raising=False)
    assert not WireDtypeConfig.coerce(None).active
    monkeypatch.setenv("RLT_MPMD_WIRE_DTYPE", "int8")
    cfg = WireDtypeConfig.coerce(None)
    assert (cfg.act, cfg.grad, cfg.active) == ("int8", "int8", True)
    cfg = WireDtypeConfig.coerce("act:bf16,grad:int8")
    assert cfg.enc == "act:bf16,grad:int8"
    assert WireDtypeConfig.coerce({"act": "bf16"}).grad == "f32"
    assert WireDtypeConfig.coerce("") == WireDtypeConfig()
    with pytest.raises(ValueError, match="expected one of"):
        WireDtypeConfig.coerce("int4")
    with pytest.raises(ValueError, match="unknown keys"):
        WireDtypeConfig.coerce({"activations": "int8"})
    with pytest.raises(TypeError):
        WireDtypeConfig.coerce(7)


def test_wire_codec_roundtrip_ratio_and_ef():
    from ray_lightning_tpu.mpmd import transfer as xfer

    rng = np.random.default_rng(0)
    tree = {
        "h": rng.standard_normal((64, 256)).astype(np.float32),
        "idx": np.arange(32, dtype=np.int32),  # non-float passes through
    }
    codec = xfer.WireCodec(xfer.WireDtypeConfig.coerce("int8"))
    payload = codec.encode_payload("act", 0, 0, 0, tree)
    back = xfer.decode_tree(payload)
    assert back["h"].dtype == np.float32
    np.testing.assert_array_equal(back["idx"], tree["idx"])
    amax = np.abs(tree["h"]).reshape(-1, 256).max(axis=1)
    err = np.abs(back["h"] - tree["h"]).reshape(-1, 256).max(axis=1)
    assert (err <= amax / 254.0 + 1e-7).all()
    assert codec.bytes_full_width / len(payload) >= 3.5

    # Grad-direction EF: resending the same slot telescopes — the mean
    # of N decoded payloads beats any single-shot decode.
    g = rng.standard_normal(4096).astype(np.float32)
    ef = xfer.WireCodec(xfer.WireDtypeConfig.coerce("int8"))
    outs = [
        xfer.decode_tree(ef.encode_payload("grad", s, 0, 0, g))
        for s in range(8)
    ]
    single = np.abs(outs[0] - g).mean()
    averaged = np.abs(np.mean(outs, axis=0) - g).mean()
    assert averaged < single / 4
    # A slot whose shape changes resets its residual, never misapplies.
    out = xfer.decode_tree(ef.encode_payload("grad", 9, 0, 0, g[:1024]))
    assert out.shape == (1024,)


def test_mpmd_strategy_validates_wire_dtype_eagerly():
    from ray_lightning_tpu.parallel.strategies import MpmdStrategy

    with pytest.raises(ValueError, match="expected one of"):
        MpmdStrategy(num_stages=2, wire_dtype="int4")
    s = MpmdStrategy(num_stages=2, devices_per_stage=1,
                     wire_dtype="act:bf16,grad:int8")
    assert s.wire_dtype == "act:bf16,grad:int8"


def _pipeline_setup():
    from ray_lightning_tpu.mpmd.plan import _gpt_untie, gpt_mpmd_spec

    cfg = GPTConfig(vocab_size=256, n_layer=4, n_head=4, d_model=64,
                    seq_len=64, warmup_steps=2)
    module = GPT(cfg, attn_impl="xla")
    module.precision = "f32"
    spec = gpt_mpmd_spec(module)
    full = _gpt_untie(module.init_params(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(11)
    steps, bsz = 4, 8
    data = [
        {"tokens": rng.integers(
            0, cfg.vocab_size, (bsz, cfg.seq_len + 1)).astype(np.int32)}
        for _ in range(steps)
    ]
    return spec, full, data, steps


@pytest.mark.slow
def test_mpmd_wire_dtype_pipeline_parity_and_ratio():
    """Quantized DCN segments against the f32 wire on the in-process
    2-worker pipeline: int8 ships >= 3x fewer payload bytes and the
    loss trajectory stays put (grad EF keeps the error telescoping
    across the 1f1b resends of each micro-batch slot)."""
    from ray_lightning_tpu.mpmd.inproc import run_inproc_pipeline_fit

    spec, full, data, steps = _pipeline_setup()

    def run(wire):
        # Meshless per-stage devices: the wire
        # codec is transport-layer, orthogonal to stage sharding.
        return run_inproc_pipeline_fit(
            spec, full, spec.tx_factory, lambda s: data[s], steps,
            n_workers=2, n_micro=4, schedule="1f1b", wire_dtype=wire,
        )

    ref = run(None)
    assert all(x["wire_ratio"] == 1.0 for x in ref["xfer"] if x["wire_ratio"])

    q = run("int8")
    np.testing.assert_allclose(
        q["losses"], ref["losses"], rtol=2e-3, atol=1e-4
    )
    sent = sum(x["bytes_sent"] for x in q["xfer"])
    fullw = sum(x["bytes_full_width"] for x in q["xfer"])
    assert fullw / sent >= 3.0
    assert all(
        x["enc"] == "act:int8,grad:int8" for x in q["xfer"] if x["bytes_sent"]
    )

    # The shipping default for DCN: bf16 activations, int8+EF grads.
    mixed = run("act:bf16,grad:int8")
    np.testing.assert_allclose(
        mixed["losses"], ref["losses"], rtol=2e-3, atol=1e-4
    )
    m_sent = sum(x["bytes_sent"] for x in mixed["xfer"])
    assert fullw / m_sent >= 1.8  # bf16 halves acts; grads still ~4x


def test_quantized_send_torn_segment_fails_loudly(tmp_path, monkeypatch):
    """Chaos contract: a torn shm segment under a QUANTIZED payload must
    poison the receiving mailbox (decode raises, recv surfaces it) —
    never dequantize garbage into a silently-wrong activation."""
    from ray_lightning_tpu.mpmd.transfer import (
        QueueChannel, StageInbox, WireCodec, WireDtypeConfig,
    )

    monkeypatch.setenv("RLT_FAULT", "torn@point:handoff_send")
    monkeypatch.setenv("RLT_FAULT_STATE", str(tmp_path / "chaos"))
    inbox = StageInbox()
    chan = QueueChannel(
        inbox.handle, same_host=True, shm_threshold=0,
        codec=WireCodec(WireDtypeConfig.coerce("int8")),
    )
    try:
        chan.send(
            "act", 0, 0,
            {"h": np.ones((64, 256), np.float32)},
        )
        with pytest.raises(RuntimeError, match="transfer lane failed"):
            inbox.mailbox.recv(("act", 0, 0, 0), timeout=20.0)
        assert chan.shm_sends == 1
    finally:
        chan.close()
        inbox.close()


def test_unquantized_send_unaffected_by_codec_default():
    """wire_dtype unset → f32 wire, bitwise-identical payload bytes to
    the pre-codec channel (the zero-risk default)."""
    from ray_lightning_tpu.mpmd.transfer import LocalChannel, Mailbox

    box = Mailbox()
    chan = LocalChannel(box)
    tree = {"h": np.arange(12, dtype=np.float32)}
    chan.send("act", 0, 0, tree)
    payload, blocked = box.recv(("act", 0, 0, 0), timeout=5.0)
    np.testing.assert_array_equal(payload["h"], tree["h"])
    stats = chan.xfer_stats()
    assert stats["enc"] == "act:f32,grad:f32"
    assert stats["bytes_sent"] == stats["bytes_full_width"]
