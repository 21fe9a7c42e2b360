"""GPT — the flagship transformer family (decoder-only LM), TPU-first.

≙ the reference's "large model" example slot (pl_bolts ImageGPT under
``RayShardedPlugin``, ``/root/reference/examples/ray_ddp_sharded_example.py:48-71``
— its GPT is an external torch module).  Here the model is owned by the
framework and written for the hardware:

* **scan-over-layers**: block parameters are stacked with a leading
  ``n_layer`` axis and the forward is one ``lax.scan`` — XLA compiles one
  block body instead of ``n_layer`` inlined copies (compile time stays
  flat as depth grows).
* **mixed precision**: activations in bfloat16 (MXU-native), parameters,
  layer-norm statistics, softmax and the loss in float32.
* **attention dispatch**: :func:`ray_lightning_tpu.ops.causal_attention`
  — Pallas flash kernel on TPU, XLA einsum elsewhere, or ring attention
  over a sequence-parallel mesh axis for long context.
* **parallelism as annotations**: :meth:`GPT.param_partition_specs`
  publishes Megatron-style tensor-parallel PartitionSpecs (column-split
  QKV/MLP-in, row-split proj/MLP-out, vocab-split embedding); the
  strategy layers ZeRO/FSDP sharding on top (see
  ``parallel/sharding.py``) and XLA inserts the collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ray_lightning_tpu.core.data import TpuDataModule, NumpyLoader
from ray_lightning_tpu.core.module import TpuModule
from ray_lightning_tpu.ops import causal_attention

__all__ = ["GPTConfig", "GPT", "SyntheticLMDataModule", "make_block_stage",
           "gpt_adamw", "merge_lora", "extract_lora", "add_lora_adapters",
           "synthetic_lora_adapter", "has_lora_adapters",
           "residual_save_bytes"]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # GPT-2 vocab padded to a multiple of 128 (MXU)
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    seq_len: int = 1024
    mlp_ratio: int = 4
    lr: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    # Mixture-of-Experts (0 = dense MLP).  Experts replace every block's
    # MLP; routed with top-k capacity dispatch (ops/moe.py) and sharded
    # over an ``expert`` mesh axis when present.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 1e-2
    # AdamW first-moment storage dtype.  bf16 momentum halves that
    # state's HBM read+write in the (bandwidth-bound) optimizer update
    # with no measurable loss-curve effect at LM scale; the variance and
    # params stay f32.  Set to "float32" for bit-conservative runs.
    # Resume across a dtype change is safe: the fit loop casts restored
    # optimizer-state leaves to this run's template dtypes on load
    # (core/loop.py resume path), so f32-era checkpoints restore cleanly.
    mu_dtype: str = "bfloat16"
    # Optimizer-state precision policy (generalizes ``mu_dtype`` — that
    # knob is the legacy special case "bf16 first moment only"):
    #  * None       — legacy behavior, ``mu_dtype`` applies as before;
    #  * "float32"  — both moments f32 (bit-conservative);
    #  * "bfloat16" — BOTH moments bf16 (2x less optimizer-state HBM);
    #  * "int8"     — both moments block-scaled int8 with per-block f32
    #    absmax scales (ops/optim_quant.py; ~3.9x less state HBM, and
    #    ZeRO / RLTSHRD2 elastic shards shrink by the same factor).
    # The update math is f32 in every mode — dequant → update → requant
    # happens inside the donated train step, so the f32 moments never
    # persist in HBM.  Loss-parity vs the f32 arm is gated by
    # tests/test_opt_state.py at the int8_ef grad-comm tolerance.
    opt_state_dtype: Optional[str] = None
    # LoRA fine-tuning (0 = off).  rank>0 adds low-rank adapters on the
    # attention projections (qkv column + output proj — the standard
    # target set); the optimizer then trains ONLY the adapters (the base
    # is frozen via optax.multi_transform, so it carries no Adam
    # moments — the memory win that makes LoRA worth it).  Pairs with
    # ``utils/hf_import.py`` + ``initial_params`` for fine-tuning
    # imported checkpoints; ``merge_lora`` folds adapters into the base
    # weights for inference/generation.
    lora_rank: int = 0
    lora_alpha: float = 16.0

    @classmethod
    def tiny(cls) -> "GPTConfig":
        """Test-sized config (CPU-mesh friendly)."""
        return cls(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                   seq_len=128, warmup_steps=2)

    @classmethod
    def gpt2_small(cls) -> "GPTConfig":
        return cls()  # 124M params

    @classmethod
    def tiny_moe(cls, n_experts: int = 4, **kw) -> "GPTConfig":
        return cls(vocab_size=512, n_layer=2, n_head=4, d_model=128,
                   seq_len=128, warmup_steps=2, n_experts=n_experts, **kw)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head


def _layer_norm(x: jax.Array, g: jax.Array, b: jax.Array,
                use_pallas: bool = False) -> jax.Array:
    """f32-stats LayerNorm; ``use_pallas`` opts single-chip callers into
    the fused kernels (``ops/layer_norm.py`` — identical math)."""
    from ray_lightning_tpu.ops.layer_norm import layer_norm

    return layer_norm(x, g, b, use_pallas=use_pallas)


def _mlp_residual(x: jax.Array, p: Dict[str, Any], c,
                  ln_pallas: bool = False) -> jax.Array:
    """LN2 + GELU MLP + residual — the dense second half of a GPT block.
    Shape-agnostic over leading dims; shared by the training scan, the
    pipeline stage, and single-token decode so the block math has one
    source."""
    from ray_lightning_tpu.models.quant import resolve_weight

    h = _layer_norm(x, p["ln2_g"], p["ln2_b"], ln_pallas)
    h = jax.nn.gelu(
        h @ resolve_weight(p, "mlp_in_w", c) + p["mlp_in_b"].astype(c)
    )
    return (x + h @ resolve_weight(p, "mlp_out_w", c)
            + p["mlp_out_b"].astype(c))


def _moe_residual(x, p, cfg, groups: int, ln_pallas: bool = False):
    """LN2 + routed expert MLP + residual — the MoE second half of a GPT
    block.  Single source for the training scan and single-token decode
    (≙ the `_mlp_residual` discipline).  Returns ``(x, aux_loss)``."""
    from ray_lightning_tpu.models.quant import resolve_weight
    from ray_lightning_tpu.ops.moe import moe_mlp

    h = _layer_norm(x, p["ln2_g"], p["ln2_b"], ln_pallas)
    y, aux = moe_mlp(
        h, p["gate_w"],
        resolve_weight(p, "moe_in_w", p["gate_w"].dtype), p["moe_in_b"],
        resolve_weight(p, "moe_out_w", p["gate_w"].dtype), p["moe_out_b"],
        top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        groups=groups,
    )
    return x + y, aux


class GPT(TpuModule):
    """Decoder-only LM.  Batch contract: ``{"tokens": int32 (B, T+1)}``
    — inputs are ``tokens[:, :-1]``, targets ``tokens[:, 1:]``."""

    def __init__(
        self,
        config: Optional[GPTConfig] = None,
        attn_impl: str = "auto",
        seq_axis: str = "sp",
        ring_layout: str = "contiguous",
        remat: bool = False,
        remat_policy: str = "dots+flash",
    ):
        super().__init__()
        self.config = config or GPTConfig.tiny()
        self.attn_impl = attn_impl
        self.seq_axis = seq_axis
        # "zigzag" balances causal work across ring hops (~2x wall-clock
        # for long context); the wrapper permutes the sequence dim in and
        # out, so activations stay normally ordered for the rest of the
        # model.  Data-layer pre-permutation (zigzag_indices) is the
        # gather-free integration for production-scale runs.
        self.ring_layout = ring_layout
        # Rematerialization: recompute block activations in the backward
        # pass instead of holding them in HBM (bandwidth-bound TPU trade:
        # ~30% more FLOPs for ~n_layer× less activation memory — enables
        # bigger per-chip batches / longer sequences).  MXU outputs
        # (matmul results) are kept; cheap elementwise is recomputed.
        #
        # ``remat_policy`` selects what the backward keeps (an on-hardware
        # A/B surface — PERFORMANCE.md "prepared experiments"):
        #  * "dots+flash"     — matmul outputs + ALL named flash residuals
        #    (out/lse/q/k/v).  Never re-runs the attention kernel, but may
        #    double-save the qkv projections (the dots policy already
        #    keeps the (B,T,3d) matmul output the per-head q/k/v are mere
        #    transposes of).
        #  * "dots+flash-out" — matmul outputs + flash out/lse only; the
        #    backward re-derives the per-head transposes from the saved
        #    qkv matmul output (cheap VPU work, ~150 MB/layer less
        #    residual traffic at GPT-2-small/seq-1024 if the double-save
        #    is real).
        #  * "dots"           — matmul outputs only; the backward re-runs
        #    the flash forward kernel (measured dead end, kept as the
        #    control arm).
        #  * "bf16-resid"     — the dots+flash-out save set, PLUS the
        #    layer-scan carry (the residual stream between blocks) is
        #    stored in bf16 and upcast to the compute dtype on read.
        #    The scan's per-layer carry save is the profiler's largest
        #    remaining dynamic-update-slice line; on an f32-precision
        #    run this halves it (on bf16 runs the carry is already
        #    bf16, so the arm costs nothing and saves only the f32
        #    embed-boundary save).  Numerics: equivalent to casting the
        #    residual stream to bf16 at block boundaries — exactly what
        #    precision="bf16" already does — so the f32-run loss delta
        #    is the bf16 rounding of one tensor per layer
        #    (tolerance-pinned by tests/test_gpt.py).
        if remat_policy not in (
            "dots+flash", "dots+flash-out", "dots", "bf16-resid"
        ):
            raise ValueError(
                f"remat_policy {remat_policy!r} not in "
                f"('dots+flash', 'dots+flash-out', 'dots', 'bf16-resid')"
            )
        if self.config.lora_rank > 0 and self.config.n_experts > 0:
            raise ValueError(
                "LoRA adapters target the dense attention projections; "
                "lora_rank > 0 with n_experts > 0 is not supported"
            )
        # Eager knob validation (same discipline as remat_policy): a
        # typo'd state-precision policy fails at construction, not when
        # the optimizer first builds on a worker.
        from ray_lightning_tpu.models.optim import resolve_opt_state_dtype

        resolve_opt_state_dtype(self.config.opt_state_dtype)
        self.remat = remat
        self.remat_policy = remat_policy
        self.save_hyperparameters(
            **dataclasses.asdict(self.config), attn_impl=attn_impl,
            remat=remat, remat_policy=remat_policy,
        )

    # -- params -------------------------------------------------------------
    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        d, h, L = cfg.d_model, cfg.mlp_ratio * cfg.d_model, cfg.n_layer
        keys = jax.random.split(rng, 8)

        def norm(key, shape, std=0.02):
            return (jax.random.normal(key, shape) * std).astype(jnp.float32)

        # Residual-path projections scaled by 1/sqrt(2L) (GPT-2 init).
        resid_std = 0.02 / np.sqrt(2 * L)
        blocks = {
            "ln1_g": jnp.ones((L, d)),
            "ln1_b": jnp.zeros((L, d)),
            "qkv_w": norm(keys[2], (L, d, 3 * d)),
            "qkv_b": jnp.zeros((L, 3 * d)),
            "proj_w": norm(keys[3], (L, d, d), std=resid_std),
            "proj_b": jnp.zeros((L, d)),
            "ln2_g": jnp.ones((L, d)),
            "ln2_b": jnp.zeros((L, d)),
        }
        if cfg.lora_rank > 0:
            blocks.update(_init_lora_blocks(cfg, keys[6]))
        E = cfg.n_experts
        if E > 0:
            blocks.update({
                "gate_w": norm(keys[6], (L, d, E)),
                "moe_in_w": norm(keys[4], (L, E, d, h)),
                "moe_in_b": jnp.zeros((L, E, h)),
                "moe_out_w": norm(keys[5], (L, E, h, d), std=resid_std),
                "moe_out_b": jnp.zeros((L, E, d)),
            })
        else:
            blocks.update({
                "mlp_in_w": norm(keys[4], (L, d, h)),
                "mlp_in_b": jnp.zeros((L, h)),
                "mlp_out_w": norm(keys[5], (L, h, d), std=resid_std),
                "mlp_out_b": jnp.zeros((L, d)),
            })
        return {
            "wte": norm(keys[0], (cfg.vocab_size, d)),
            "wpe": norm(keys[1], (cfg.seq_len, d), std=0.01),
            "blocks": blocks,
            "ln_f_g": jnp.ones((d,)),
            "ln_f_b": jnp.zeros((d,)),
        }

    def param_partition_specs(self) -> Dict[str, Any]:
        """Tensor-parallel layout over the ``tensor`` mesh axis.

        Megatron recipe: QKV and MLP-in are column-parallel (shard the
        output features ⇒ heads split across devices, no collective
        between the two matmuls of a block half), proj and MLP-out are
        row-parallel (shard the input features ⇒ one psum at the block
        output, inserted by GSPMD).  The tied embedding is sharded on
        d_model, not vocab: under GSPMD a gather from a vocab-sharded
        table forces an involuntary reshard of the lookup output every
        step, whereas a feature-sharded table keeps both the lookup and
        the LM-head contraction in natively partitioned form.  Axes absent
        from the active mesh are dropped by the strategy.
        """
        t, e = "tensor", "expert"
        blocks = {
            "ln1_g": P(), "ln1_b": P(),
            "qkv_w": P(None, None, t), "qkv_b": P(None, t),
            "proj_w": P(None, t, None), "proj_b": P(),
            "ln2_g": P(), "ln2_b": P(),
        }
        if self.config.lora_rank > 0:
            # Adapters follow the host matmul's layout: qkv's B matrix is
            # column-parallel like qkv_w; proj's A contracts the
            # tensor-sharded attention output (GSPMD inserts the psum).
            blocks.update({
                "lora_qkv_a": P(), "lora_qkv_b": P(None, None, t),
                "lora_proj_a": P(None, t, None), "lora_proj_b": P(),
            })
        if self.config.n_experts > 0:
            # ep × tp composition: experts over the expert axis, each
            # expert's hidden dim over tensor (column/row-parallel FFN).
            blocks.update({
                "gate_w": P(),
                "moe_in_w": P(None, e, None, t),
                "moe_in_b": P(None, e, t),
                "moe_out_w": P(None, e, t, None),
                "moe_out_b": P(None, e, None),
            })
        else:
            blocks.update({
                "mlp_in_w": P(None, None, t), "mlp_in_b": P(None, t),
                "mlp_out_w": P(None, t, None), "mlp_out_b": P(),
            })
        return {
            "wte": P(None, t),
            "wpe": P(),
            "blocks": blocks,
            "ln_f_g": P(), "ln_f_b": P(),
        }

    # -- forward ------------------------------------------------------------
    def _compute_dtype(self):
        return jnp.bfloat16 if self.precision in ("bf16", "bfloat16") else (
            jnp.float32
        )

    def _attention(self, q, k, v):
        if self.attn_impl == "ring":
            from ray_lightning_tpu.ops import ring_attention_sharded

            mesh = getattr(self.trainer, "mesh", None)
            if mesh is None or self.seq_axis not in mesh.axis_names:
                # Explicitly-requested ring attention with no seq axis is a
                # misconfiguration — falling back silently would hide an
                # O(seq^2)-memory surprise on a long-context run.
                raise ValueError(
                    f"attn_impl='ring' needs mesh axis {self.seq_axis!r}; "
                    f"active mesh axes: "
                    f"{None if mesh is None else mesh.axis_names}. Add "
                    f"{self.seq_axis!r} to mesh_axes or use attn_impl='auto'."
                )
            return ring_attention_sharded(
                q, k, v, mesh, seq_axis=self.seq_axis,
                layout=self.ring_layout,
            )
        return causal_attention(
            q, k, v, impl=self.attn_impl, **self._attention_mesh()
        )

    def _attention_mesh(self) -> Dict[str, Any]:
        """The mesh decides where the flash kernel may run (ops/
        attention.py): bare on one device or inside an already
        per-device body (shard_map step mode, the grad-sync island), in
        a shard_map island on a batch-only GSPMD mesh."""
        trainer = getattr(self, "trainer", None)
        return {
            "mesh": getattr(trainer, "mesh", None),
            "manual": (
                getattr(trainer, "step_mode", "gspmd") != "gspmd"
                or bool(getattr(trainer, "grad_sync_active", False))
            ),
        }

    def _on_one_tpu(self) -> bool:
        """Single-chip TPU run: where a bare ``pallas_call`` (opaque to
        the GSPMD partitioner) may sit directly in the step."""
        mesh = getattr(getattr(self, "trainer", None), "mesh", None)
        return (
            (mesh is None or getattr(mesh, "size", 1) == 1)
            and jax.default_backend() == "tpu"
        )

    def _ce_island(self, batch_dim: int) -> bool:
        """Multi-chip TPU mesh on which the CE kernels run per device in
        a shard_map island (batch-only sharding, replicated head)."""
        trainer = getattr(self, "trainer", None)
        mesh = getattr(trainer, "mesh", None)
        return (
            mesh is not None and getattr(mesh, "size", 1) > 1
            and jax.default_backend() == "tpu"
            and self._batch_only_mesh(trainer, batch_dim)
        )

    def kernel_paths(self, batch_size: int) -> Dict[str, str]:
        """Which implementation each optional-kernel site of the
        training step takes for ``batch_size`` sequences under the
        attached trainer's mesh — the predicates the forward itself
        evaluates (backend, mesh, shapes, ``RLT_DISABLE_KERNELS``),
        named for logs and artifacts.  Nothing is compiled."""
        from ray_lightning_tpu.ops.attention import (
            _flash_supported,
            _multi_device,
        )
        from ray_lightning_tpu.ops.cross_entropy import _pallas_fwd_ok
        from ray_lightning_tpu.ops.layer_norm import _kernel_selected

        cfg = self.config
        c = self._compute_dtype()
        am = self._attention_mesh()
        attn = self.attn_impl
        if attn == "auto":
            q = jax.ShapeDtypeStruct(
                (batch_size, cfg.seq_len, cfg.n_head, cfg.head_dim), c
            )
            attn = "flash" if _flash_supported(q, **am) else "xla"
        if (attn == "flash" and _multi_device(am["mesh"])
                and not am["manual"]):
            attn = "flash-island"
        ce_kernel = _pallas_fwd_ok(cfg.d_model, c)
        if ce_kernel and self._on_one_tpu():
            ce = "pallas"
        elif ce_kernel and self._ce_island(batch_size):
            ce = "pallas-island"
        else:
            ce = "scan"
        ln = ("pallas" if _kernel_selected(cfg.d_model, self._on_one_tpu())
              else "xla")
        return {"attention": attn, "cross_entropy": ce, "layer_norm": ln}

    def _moe_groups(self) -> int:
        """Routing groups = data-parallel shard count, so each group's
        capacity cumsum stays shard-local (GShard's group dim)."""
        mesh = getattr(getattr(self, "trainer", None), "mesh", None)
        if mesh is None:
            return 1
        from ray_lightning_tpu.parallel import sharding as shardlib

        g = 1
        for axis in shardlib.data_axes(mesh):
            g *= mesh.shape[axis]
        return g

    def _constrain_residual(self, x: jax.Array) -> jax.Array:
        """Anchor the residual stream to its canonical layout: batch over
        the data(+fsdp) axes, seq over the sp axis when ring attention is
        active, features replicated.

        Without the anchor, GSPMD propagates the TP parameter shardings
        into activations and flip-flops between feature-sharded and
        batch-sharded layouts across the block, hitting its "involuntary
        full rematerialization" fallback (an all-gather + re-partition per
        mismatch) in the backward pass.  One explicit constraint per block
        keeps every reshard a cheap local collective on ICI.
        """
        trainer = getattr(self, "trainer", None)
        mesh = getattr(trainer, "mesh", None)
        # Under shard_map (the Horovod-duality flavor) the body is already
        # per-device with Manual axes — a named sharding constraint there
        # is both meaningless and a trace-time error.  gspmd only; the
        # quantized grad-sync island (grad_sync_active) also runs this
        # body per-device under shard_map, so it skips the anchor too.
        if (
            mesh is None
            or getattr(trainer, "step_mode", "gspmd") != "gspmd"
            or getattr(trainer, "grad_sync_active", False)
        ):
            return x
        from jax.sharding import NamedSharding

        from ray_lightning_tpu.parallel import sharding as shardlib

        batch = shardlib.data_axes(mesh)
        seq = self.seq_axis if self.seq_axis in mesh.axis_names else None
        # Batches that don't divide the batch axes (e.g. a 2-row
        # inference call on a module still carrying its 8-way training
        # mesh) cannot take the constraint — skip it rather than fail;
        # the anchor is a perf hint, not a correctness requirement.
        n_shards = 1
        for a in (batch if batch else ()):
            n_shards *= mesh.shape[a]
        if x.shape[0] % n_shards:
            return x
        if seq is not None and x.shape[1] % mesh.shape[seq]:
            return x
        spec = P(batch if batch else None, seq, None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec)
        )

    def forward(self, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
        """tokens (B, T) int32 -> logits (B, T, vocab) float32."""
        return self.forward_with_aux(params, tokens)[0]

    def forward_with_aux(
        self, params: Dict[str, Any], tokens: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """(logits, moe_aux_loss) — aux is 0.0 for dense configs.

        Materializes the full ``(B, T, V)`` logits tensor — inference /
        predict path only.  The training loss goes through
        :meth:`forward_hidden` + the vocab-chunked fused cross-entropy
        (``ops/cross_entropy.py``) so that tensor never exists.
        """
        x, aux = self.forward_hidden(params, tokens)
        c = self._compute_dtype()
        logits = jnp.einsum(
            "btd,vd->btv", x, params["wte"].astype(c),
            preferred_element_type=jnp.float32,
        )
        return logits, aux

    def forward_hidden(
        self, params: Dict[str, Any], tokens: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Transformer trunk: tokens -> (final hidden (B, T, d), moe_aux)."""
        cfg = self.config
        c = self._compute_dtype()
        B, T = tokens.shape
        # Fused-LN gate: same constraint as the CE kernels — pallas_call
        # is opaque to the GSPMD partitioner, so single chip only.
        lnp = self._on_one_tpu()
        x = self._constrain_residual(
            (params["wte"][tokens] + params["wpe"][:T]).astype(c)
        )
        # Scan-residual compression: under the "bf16-resid" arm the
        # CARRY crossing scan iterations — which is exactly what the
        # scan saves per layer for the remat backward — is held in
        # bf16; the block upcasts to the compute dtype on entry (the
        # "f32 recompute on read" half of the trade).  Gated on remat:
        # without remat nothing is saved per layer, so rounding the
        # carry would change numerics for no storage win.
        bf16r = self.remat and self.remat_policy == "bf16-resid"
        if bf16r:
            x = x.astype(jnp.bfloat16)

        lora_s = (
            cfg.lora_alpha / cfg.lora_rank if cfg.lora_rank > 0 else 0.0
        )

        def block(carry, p):
            x, aux = carry
            if bf16r:
                x = x.astype(c)
            h = _layer_norm(x, p["ln1_g"], p["ln1_b"], lnp)
            qkv = h @ p["qkv_w"].astype(c) + p["qkv_b"].astype(c)
            if cfg.lora_rank > 0:
                qkv = qkv + (
                    (h @ p["lora_qkv_a"].astype(c))
                    @ p["lora_qkv_b"].astype(c)
                ) * lora_s
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def heads(z):
                return z.reshape(B, T, cfg.n_head, cfg.head_dim)

            att = self._attention(heads(q), heads(k), heads(v))
            att = att.reshape(B, T, cfg.d_model)
            proj = att @ p["proj_w"].astype(c) + p["proj_b"].astype(c)
            if cfg.lora_rank > 0:
                proj = proj + (
                    (att @ p["lora_proj_a"].astype(c))
                    @ p["lora_proj_b"].astype(c)
                ) * lora_s
            x = x + proj
            if cfg.n_experts > 0:
                x, layer_aux = _moe_residual(
                    x, p, cfg, groups=self._moe_groups(), ln_pallas=lnp
                )
                aux = aux + layer_aux
            else:
                x = _mlp_residual(x, p, c, lnp)
            x = self._constrain_residual(x)
            if bf16r:
                x = x.astype(jnp.bfloat16)
            return (x, aux), None

        if self.remat:
            # Save matmul outputs AND (per remat_policy) the named
            # flash-attention residuals — recomputing elementwise is the
            # remat bargain; re-running the attention kernel is not.
            cp = jax.checkpoint_policies
            if self.remat_policy == "dots":
                policy = cp.dots_with_no_batch_dims_saveable
            else:
                # "bf16-resid" keeps the dots+flash-out (no-double-save)
                # set — its storage win comes from the bf16 carry, not
                # from a different save set.
                names = ("flash_out", "flash_lse")
                if self.remat_policy == "dots+flash":
                    names += ("flash_q", "flash_k", "flash_v")
                policy = cp.save_from_both_policies(
                    cp.dots_with_no_batch_dims_saveable,
                    cp.save_only_these_names(*names),
                )
            block = jax.checkpoint(block, policy=policy)
        # Grad-overlap trunk segmentation (parallel/overlap.py): split
        # the layer scan into G sub-scans so each segment's stacked
        # grads emerge at a segment boundary — tapped there, their
        # bucket collectives overlap the earlier segments' backward
        # instead of waiting for the whole trunk.  The taps sit OUTSIDE
        # the (possibly remat-wrapped) block on the scan's xs input, and
        # each sub-scan runs the same per-layer op sequence as the
        # single scan, so segmentation alone (no plane — e.g. the
        # grad_comm=full arm) is bitwise-neutral.
        trainer = getattr(self, "trainer", None)
        plane = getattr(trainer, "grad_tap_plane", None)
        segs = (
            plane.trunk_segments if plane is not None
            else int(getattr(trainer, "grad_overlap_segments", 0) or 0)
        )
        carry = (x, jnp.zeros((), jnp.float32))
        if segs >= 1:
            from ray_lightning_tpu.parallel.pipeline import layer_splits

            bounds = layer_splits(
                cfg.n_layer, min(segs, max(cfg.n_layer, 1))
            )
            for g in range(len(bounds) - 1):
                b, e = bounds[g], bounds[g + 1]
                sub = {
                    k: jax.lax.slice_in_dim(v, b, e, axis=0)
                    for k, v in params["blocks"].items()
                }
                if plane is not None:
                    sub = plane.tap(f"seg{g}", sub)
                carry, _ = jax.lax.scan(block, carry, sub)
        else:
            carry, _ = jax.lax.scan(block, carry, params["blocks"])
        x, aux = carry
        if bf16r:
            x = x.astype(c)
        # Per-layer mean: the aux weight is depth-independent (balanced
        # routing ⇒ aux ≈ 1 at any n_layer).
        aux = aux / max(cfg.n_layer, 1)
        x = _layer_norm(x, params["ln_f_g"], params["ln_f_b"], lnp)
        return x, aux

    def grad_overlap_groups(self, abstract_params, segments: int):
        """Param partition for the backward-overlapped grad sync
        (``parallel/overlap.py``), ordered by backward completion.

        The final-LN group's cotangent completes *first* in the backward
        (loss → layer N → … → layer 1 → embedding), so its sync hides
        under the entire trunk backward; the trunk segments then
        complete in reverse forward order (``seg{G-1}`` before
        ``seg0``), each overlapping the segments still differentiating
        below it; the embeddings complete last — their sync is the only
        one with no compute left to hide under, ≈ the step-end
        behavior.  ``head``/``embed`` are *entry* groups (top-level
        param keys, applied by dict replacement so the tied-softmax
        ``wte`` read in the CE head sees the tapped value too); the
        ``seg{g}`` groups are tapped by :meth:`forward_hidden` at each
        sub-scan boundary.
        """
        if segments < 1:
            return None
        from ray_lightning_tpu.parallel.pipeline import layer_splits

        cfg = self.config
        bounds = layer_splits(
            cfg.n_layer, min(int(segments), max(cfg.n_layer, 1))
        )
        sds = jax.ShapeDtypeStruct

        def _like(leaf):
            return sds(tuple(leaf.shape), leaf.dtype)

        def _rows(leaf, b, e):
            return sds((e - b,) + tuple(leaf.shape[1:]), leaf.dtype)

        groups = [(
            "head",
            {k: _like(abstract_params[k]) for k in ("ln_f_g", "ln_f_b")},
            True,
        )]
        for g in range(len(bounds) - 1):
            b, e = bounds[g], bounds[g + 1]
            groups.append((
                f"seg{g}",
                {
                    k: _rows(v, b, e)
                    for k, v in abstract_params["blocks"].items()
                },
                False,
            ))
        groups.append((
            "embed",
            {k: _like(abstract_params[k]) for k in ("wte", "wpe")},
            True,
        ))
        return groups

    # -- steps --------------------------------------------------------------
    def _loss(self, params, tokens):
        from ray_lightning_tpu.ops.cross_entropy import (
            fused_lm_head_cross_entropy,
            fused_lm_head_cross_entropy_sharded,
        )

        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x, aux = self.forward_hidden(params, inputs)
        # Fused tied-LM-head CE: the (B, T, V) logits tensor (3.3 GB f32
        # for GPT-2-small at B=16) is never materialized — the head
        # matmul, logsumexp and label gather run per vocab chunk.
        # Kernel dispatch by topology:
        #  * single chip — Pallas tile kernels directly;
        #  * GSPMD mesh with batch-only sharding and a replicated head
        #    (pure DP / ZeRO-1/2) — the same kernels per device inside a
        #    shard_map island (one dwte psum in the backward);
        #  * anything else (TP head, ZeRO-3 params, SP, shard_map step
        #    mode) — the GSPMD-safe vocab-chunk scan.
        c = self._compute_dtype()
        if self._ce_island(x.shape[0]):
            loss = fused_lm_head_cross_entropy_sharded(
                x, params["wte"], targets, self.trainer.mesh,
                compute_dtype=c,
            ).mean()
        else:
            loss = fused_lm_head_cross_entropy(
                x, params["wte"], targets, compute_dtype=c,
                use_pallas=self._on_one_tpu(),
            ).mean()
        return loss, aux

    @staticmethod
    def _batch_only_mesh(trainer, batch_dim: int) -> bool:
        """True when the mesh shards only the batch and the head stays
        replicated: batch-only axes, GSPMD step mode, params unsharded
        (zero_stage < 3), batch divisible over the shards (the island
        cannot pad uneven shards the way plain GSPMD does).
        Conservative: unknown attrs veto."""
        mesh = getattr(trainer, "mesh", None)
        if mesh is None:
            return False
        if not set(mesh.axis_names) <= {"data", "fsdp"}:
            return False
        if getattr(trainer, "step_mode", None) != "gspmd":
            return False
        # Inside the quantized grad-sync island the step body is already
        # per-device shard_map — nesting the CE island would double-wrap;
        # the vocab-chunk scan is the per-device-safe path there.
        if getattr(trainer, "grad_sync_active", False):
            return False
        if batch_dim % getattr(mesh, "size", 1):
            return False
        return getattr(trainer, "zero_stage", 3) < 3

    def training_step(self, params, batch, rng):
        loss, aux = self._loss(params, batch["tokens"])
        logs = {"train_loss": loss}
        if self.config.n_experts > 0:
            logs["moe_aux_loss"] = aux
            loss = loss + self.config.moe_aux_weight * aux
        return loss, logs

    def validation_step(self, params, batch):
        loss, _ = self._loss(params, batch["tokens"])
        return {"val_loss": loss, "val_ppl": jnp.exp(loss)}

    def predict_step(self, params, batch):
        return jnp.argmax(
            self.forward(params, batch["tokens"][:, :-1]), axis=-1
        )

    def configure_optimizers(self):
        cfg = self.config
        adamw = gpt_adamw(cfg)
        if cfg.lora_rank > 0:
            # LoRA: only adapter params train.  The frozen base gets
            # set_to_zero (no Adam moments allocated for it — under
            # multi_transform's masking the optimizer state exists only
            # for the trained subset, the actual memory win of LoRA).
            def labels(params):
                return jax.tree_util.tree_map_with_path(
                    lambda path, _: "train"
                    if str(getattr(path[-1], "key", "")).startswith("lora_")
                    else "freeze",
                    params,
                )

            # Frozen grads are zeroed BEFORE the global-norm clip: the
            # clip must see the ADAPTER gradient norm, not the full
            # model's — otherwise base-weight grads (which never apply)
            # scale down every adapter update.
            return optax.chain(
                optax.multi_transform(
                    {"train": optax.identity(),
                     "freeze": optax.set_to_zero()}, labels
                ),
                optax.clip_by_global_norm(1.0),
                optax.multi_transform(
                    {"train": adamw, "freeze": optax.set_to_zero()}, labels
                ),
            )
        tx = optax.chain(optax.clip_by_global_norm(1.0), adamw)
        return tx


def gpt_adamw(cfg: GPTConfig):
    """The family's scheduled+masked AdamW WITHOUT the global-norm
    clip.  Factored out for the MPMD pipeline plane: ``adamw`` is
    elementwise, so per-stage application equals the single-program
    fit exactly, whereas ``clip_by_global_norm`` couples leaves ACROSS
    stages and does not decompose — the MPMD GPT adapter
    (``mpmd/plan.py``) uses this as its per-stage optimizer and its
    parity reference uses the same (docs/ARCHITECTURE.md round 12)."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, cfg.lr, cfg.warmup_steps, max(10 * cfg.warmup_steps, 1000)
    )
    from ray_lightning_tpu.models.optim import (
        apply_opt_state_dtype,
        decay_mask,
        resolve_opt_state_dtype,
    )

    # Optimizer-state precision: an explicit ``opt_state_dtype`` policy
    # overrides the legacy ``mu_dtype`` knob (the inner adamw then keeps
    # f32 moments — the wrapper owns the storage dtype; stacking bf16
    # mu_dtype under an int8 wrapper would quantize already-rounded
    # values for no win).
    osd = resolve_opt_state_dtype(cfg.opt_state_dtype)
    mu_dtype = jnp.dtype(cfg.mu_dtype) if osd is None else jnp.float32

    # Decay matrices only (nanoGPT-style naming rule): LN params and
    # biases are exempt; decay_mask is aware of the stacked-blocks
    # leading layer dim, so per-block biases/LN stay exempt too.
    adamw = optax.adamw(schedule, b1=0.9, b2=0.95,
                        weight_decay=cfg.weight_decay,
                        mask=decay_mask,
                        mu_dtype=mu_dtype)
    return apply_opt_state_dtype(adamw, osd)


def residual_save_bytes(
    cfg: GPTConfig,
    batch_size: int,
    policy: str,
    precision: str = "bf16",
) -> int:
    """Analytic bytes the remat backward SAVES per step under a policy
    (chip truth comes from the profiler's dynamic-update-slice lines;
    this is the model that says which arm to expect to win and by how
    much).

    Per layer, the saved set is: the scan CARRY (the block's residual-
    stream input, stacked across layers by the scan — the top profiler
    line), the dot outputs the ``dots`` policy keeps (qkv 3d, proj d,
    mlp-in 4d, mlp-out d), and the named flash residuals per arm
    (out ``d``; lse at its 8-lane stat width in f32; q/k/v transposes
    ``3d`` only under ``dots+flash`` — the double-save
    ``dots+flash-out`` exists to drop).  ``bf16-resid`` stores the
    carry in 2 bytes regardless of compute precision.
    """
    if policy not in ("dots+flash", "dots+flash-out", "dots",
                      "bf16-resid"):
        # Same eager discipline as GPT.__init__: a typo'd arm must not
        # return plausible-but-mislabeled accounting.
        raise ValueError(
            f"remat_policy {policy!r} not in "
            f"('dots+flash', 'dots+flash-out', 'dots', 'bf16-resid')"
        )
    c = 2 if precision in ("bf16", "bfloat16") else 4
    carry = 2 if policy == "bf16-resid" else c
    B, T, d, L, H = (batch_size, cfg.seq_len, cfg.d_model, cfg.n_layer,
                     cfg.n_head)
    per_layer = B * T * d * carry  # scan carry
    per_layer += B * T * 9 * d * c  # dot outputs (3d + d + 4d + d)
    if policy != "dots":
        per_layer += B * T * d * c          # flash_out
        per_layer += B * H * T * 8 * 4      # flash_lse (8-lane f32 stat)
    if policy == "dots+flash":
        per_layer += B * T * 3 * d * c      # per-head q/k/v double-save
    return L * per_layer


def has_lora_adapters(params: Dict[str, Any]) -> bool:
    """True when the tree carries unmerged LoRA adapters — the shared
    predicate behind every 'merge first' guard (generation, pipeline,
    quantization, HF export)."""
    return any(
        str(k).startswith("lora_") for k in params.get("blocks", {})
    )


def _init_lora_blocks(cfg: GPTConfig, rng: jax.Array) -> Dict[str, Any]:
    """The four stacked adapter tensors — ONE source for both
    ``GPT.init_params`` and :func:`add_lora_adapters`.  B is
    zero-initialized: the adapter delta starts at exactly 0, so step 0
    reproduces the base model bit-for-bit."""
    L, d, r = cfg.n_layer, cfg.d_model, cfg.lora_rank
    ka, kb = jax.random.split(rng)
    return {
        "lora_qkv_a": (jax.random.normal(ka, (L, d, r)) * 0.02).astype(
            jnp.float32),
        "lora_qkv_b": jnp.zeros((L, r, 3 * d)),
        "lora_proj_a": (jax.random.normal(kb, (L, d, r)) * 0.02).astype(
            jnp.float32),
        "lora_proj_b": jnp.zeros((L, r, d)),
    }


def add_lora_adapters(
    params: Dict[str, Any], cfg: GPTConfig, rng: jax.Array
) -> Dict[str, Any]:
    """Attach fresh LoRA adapters to a lora-free param tree (e.g. one
    imported from a HF checkpoint, ``utils/hf_import.py``) so it can
    warm-start a ``lora_rank > 0`` fit via ``module.initial_params``."""
    if cfg.lora_rank <= 0:
        return params
    if has_lora_adapters(params):
        # Overwriting would silently replace TRAINED adapters with
        # fresh zero-delta ones — reverting the model to the base.
        raise ValueError(
            "params already contain LoRA adapters; refusing to "
            "overwrite them. merge_lora() first, or reuse the existing "
            "adapters."
        )
    return {
        **params,
        "blocks": {**params["blocks"], **_init_lora_blocks(cfg, rng)},
    }


def extract_lora(
    params: Dict[str, Any], cfg: GPTConfig
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``(adapter, base_params)``: pull the four stacked LoRA factors
    out of a ``lora_rank > 0`` tree for multi-tenant serving.

    The adapter dict (``qkv_a/qkv_b/proj_a/proj_b`` + ``scale``) feeds
    :class:`~ray_lightning_tpu.serve.lora.AdapterPool`; ``base_params``
    is the same tree stripped of the adapters — the lora-free resident
    base every tenant shares (byte-identical across tenants fine-tuned
    from the same checkpoint, which is what makes one resident copy
    serve them all).  Inverse direction of :func:`merge_lora`: merge
    folds ONE tenant in forever, extract keeps the base shared.
    """
    if cfg.lora_rank <= 0:
        raise ValueError("extract_lora needs a lora_rank > 0 config")
    if not has_lora_adapters(params):
        raise ValueError(
            "params carry no LoRA adapters — nothing to extract"
        )
    blocks = dict(params["blocks"])
    adapter = {
        "qkv_a": blocks.pop("lora_qkv_a"),
        "qkv_b": blocks.pop("lora_qkv_b"),
        "proj_a": blocks.pop("lora_proj_a"),
        "proj_b": blocks.pop("lora_proj_b"),
        "scale": cfg.lora_alpha / cfg.lora_rank,
    }
    return adapter, {**params, "blocks": blocks}


def synthetic_lora_adapter(
    params: Dict[str, Any], cfg: GPTConfig, rng: jax.Array,
    scale: float = 0.3,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``(adapter, merged_params)``: ONE synthetic LoRA tenant of a
    lora-free base — random non-zero A *and* B factors, so the tenant
    generates a visibly distinct greedy stream (``add_lora_adapters``
    alone zero-inits B: delta exactly 0, every "tenant" IS the base).

    The multi-tenant serving bench/example/test triple all need N
    distinct tenants plus each tenant's fully-merged tree as the
    parity reference; real tenants come out of a ``lora_rank > 0``
    fine-tune via :func:`extract_lora` instead.  ``cfg.lora_rank``
    must be > 0 (it is the adapter's rank).
    """
    ka, kq, kp = jax.random.split(rng, 3)
    tree = add_lora_adapters(params, cfg, ka)
    blocks = dict(tree["blocks"])
    blocks["lora_qkv_b"] = (
        jax.random.normal(kq, blocks["lora_qkv_b"].shape) * scale
    ).astype(blocks["lora_qkv_b"].dtype)
    blocks["lora_proj_b"] = (
        jax.random.normal(kp, blocks["lora_proj_b"].shape) * scale
    ).astype(blocks["lora_proj_b"].dtype)
    tree = {**tree, "blocks": blocks}
    adapter, _ = extract_lora(tree, cfg)
    return adapter, merge_lora(tree, cfg)


def merge_lora(params: Dict[str, Any], cfg: GPTConfig) -> Dict[str, Any]:
    """Fold LoRA adapters into the base weights and strip them.

    The result is a plain (lora-free) GPT param tree with identical
    forward math — the inference/generation path (``models/generate.py``
    consumes raw ``qkv_w``/``proj_w``) and any lora-unaware tooling run
    it unchanged.  Merged-weight logits equal the adapter-form logits in
    f32 exactly up to one fused-matmul reassociation.
    """
    if cfg.lora_rank <= 0:
        return params
    s = cfg.lora_alpha / cfg.lora_rank
    blocks = dict(params["blocks"])
    blocks["qkv_w"] = blocks["qkv_w"] + jnp.einsum(
        "ldr,lrk->ldk", blocks["lora_qkv_a"], blocks["lora_qkv_b"]
    ) * s
    blocks["proj_w"] = blocks["proj_w"] + jnp.einsum(
        "ldr,lrk->ldk", blocks["lora_proj_a"], blocks["lora_proj_b"]
    ) * s
    for k in ("lora_qkv_a", "lora_qkv_b", "lora_proj_a", "lora_proj_b"):
        blocks.pop(k)
    return {**params, "blocks": blocks}


def make_block_stage(cfg: GPTConfig, compute_dtype=jnp.float32):
    """Stage function for :func:`..parallel.pipeline.pipeline_apply`:
    ``(blocks_shard, x) -> x`` running a contiguous run of DENSE GPT
    blocks (any leading layer count — the pipeline shards the stacked
    layer axis).  The single source of the block math for the pipeline
    tests/example/dryrun; the training path keeps its own scan in
    :meth:`GPT.forward_hidden` (remat + MoE + sharding constraints).
    """
    if cfg.n_experts > 0:
        raise ValueError("make_block_stage covers dense blocks only")
    if cfg.lora_rank > 0:
        raise ValueError(
            "make_block_stage does not apply LoRA adapters; fold them "
            "with merge_lora(params, cfg) first (running unmerged would "
            "silently use the frozen base weights)"
        )

    def stage(blocks, x):
        b, t = x.shape[0], x.shape[1]
        c = compute_dtype
        x = x.astype(c)  # activations in the compute dtype throughout

        def body(x, p):
            h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
            qkv = h @ p["qkv_w"].astype(c) + p["qkv_b"].astype(c)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            att = causal_attention(
                *(z.reshape(b, t, cfg.n_head, cfg.head_dim)
                  for z in (q, k, v)), impl="xla",
            ).reshape(b, t, cfg.d_model)
            x = x + att @ p["proj_w"].astype(c) + p["proj_b"].astype(c)
            return _mlp_residual(x, p, c), None

        x, _ = jax.lax.scan(body, x, blocks)
        return x

    return stage


class SyntheticLMDataModule(TpuDataModule):
    """Deterministic synthetic token stream for smoke tests and benches.

    ≙ the reference's ``RandomDataset`` fixture pattern
    (``tests/utils.py:16-25``), extended to the LM batch contract.
    """

    def __init__(self, config: GPTConfig, batch_size: int = 8,
                 num_batches: int = 16, seed: int = 0):
        super().__init__()
        self.config = config
        self.batch_size = batch_size
        self.num_batches = num_batches
        self.seed = seed
        self._tokens: Optional[np.ndarray] = None

    def setup(self, stage: str) -> None:
        if self._tokens is None:
            rng = np.random.default_rng(self.seed)
            n = self.batch_size * self.num_batches
            self._tokens = rng.integers(
                0, self.config.vocab_size,
                size=(n, self.config.seq_len + 1),
            ).astype(np.int32)

    def _loader(self):
        from ray_lightning_tpu.core.data import ArrayDataset

        ds = ArrayDataset(tokens=self._tokens)
        return NumpyLoader(
            ds, batch_size=self.batch_size,
            shard_index=self.shard_index, num_shards=self.num_shards,
        )

    def train_dataloader(self):
        return self._loader()

    def val_dataloader(self):
        return self._loader()
