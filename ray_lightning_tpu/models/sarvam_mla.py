"""The ``sarvam_mla`` family: latent (MLA) attention over the
pattern-described decoder of ``models/exaone_moe.py``.

One more **mixer kind**, ``latent``, which defines once, in this file,
the three things the system needs of a kind:

* its full-sequence forward, in the published (un-absorbed) form: the
  prompt's latent ``c`` is expanded to per-head keys ``[k_n | k_r]``
  (128 + 64 rotary columns) and values (128) and attended causally
  (``ops/flash_attention.py`` at serving sizes, head width 192, value
  width 128); training ``forward`` and the serving prefill run it;
* its cache state: ONE row ``[c | k_r]`` a position a layer (``kv_lora_
  rank + qk_rope_head_dim`` numbers, 576 as published), shared by all
  heads, stored after the latent's norm and the key's rotation in a
  pool ``(L, N, Bs, row)`` whose row is padded to whole 128 lanes; one
  block table a slot, one allocator: to the scheduler and the engine
  the family looks like GPT's;
* its one-token decode, in the absorbed form: with ``Wkvb`` split per
  head into ``W_uk (r x dn)`` and ``W_uv (r x dv)``, ``q_n k_n^T = (q_n
  W_uk^T) c^T`` and ``a = (P c) W_uv``, so a head's query is ``[q_n
  W_uk^T | q_r]`` scored against the cached row as it lies, and the
  output ``P @ c`` goes through ``W_uv`` afterwards
  (``ops/paged_attention.py`` ``rlt_mla_decode``).  Equal to the
  published form up to rounding.

The block is the family's: RMSNorm on each branch's *input* (``x +
Attn(norm(x))``, ``x + F(norm(x))``), RMSNorm over each query head's
192 columns after ``q_proj`` and over the latent (``kv_a_layernorm``),
YaRN rotary positions on a 64-wide slice of each head and ONE rotary
key shared by all heads, a softmax scale that carries ``mscale^2``, a
dense first layer, then sigmoid-scored top-k routing chosen by ``score
+ bias`` with a shared expert.  Everything that is not the mixer is
``models/exaone_moe.py``'s, imported: ``rms_norm``, ``rope`` (handed
the YaRN frequencies as data), ``swiglu``, ``feed_forward``,
``decoder_block``, ``head_logits``, ``init_tree``, and the share
(``experts_held``, ``vocab_held``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models.exaone_moe import (
    ExaoneMoE, ServeFamily as ExaoneServeFamily, _mm, _resolve_attn,
    decoder_block, head_logits, init_tree, rms_norm, rope,
)
from ray_lightning_tpu.ops.attention import _NEG_INF

__all__ = ["SarvamMLA", "SarvamMLAConfig", "sarvam_mla_tiny"]

FAMILY = "sarvam_mla"
LANES = 128


@dataclasses.dataclass(frozen=True)
class SarvamMLAConfig:
    """The model's shape (no optimizer fields) and this chip's share."""

    vocab_size: int = 262144
    d_model: int = 4096
    n_layer: int = 32
    n_head: int = 64
    kv_lora_rank: int = 512
    # The query's own latent (``q_lora_rank``): None, q_proj is direct
    # (:func:`latent_projections`).
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 16384             # the dense feed-forward's width
    d_expert: int = 2048          # every expert's, and the shared one's
    n_experts: int = 128          # the router's outputs
    top_k: int = 8
    routed_scale: float = 2.5
    first_dense: int = 1          # leading dense layers, the rest sparse
    rms_eps: float = 1e-6
    rope_theta: float = 1e4
    # ``rope_scaling`` (deepseek_yarn).
    rope_factor: float = 40.0
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    seq_len: int = 131072         # positions the model declares
    # The share: experts [lo, hi) of n_experts, vocabulary rows [lo, hi).
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[Tuple[int, int]] = None
    param_dtype: str = "bfloat16"

    # The family's norm placement and gains (``exaone_moe.decoder_block``),
    # its shared expert and its MLPs' clamp (``exaone_moe.feed_forward``):
    # pre-norm, gains as published, a shared expert, no clamp.  The
    # rotary key's columns rotate in halves (``rope_interleave`` false).
    norms = "pre"
    zero_centred = False
    shared_expert = True
    swiglu_limit = None
    rope_interleave = False

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", (0, self.n_experts))
        if self.vocab_held is None:
            object.__setattr__(self, "vocab_held", (0, self.vocab_size))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "vocab_held", tuple(self.vocab_held))
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_experts} experts")
        lo, hi = self.vocab_held
        if not 0 <= lo < hi <= self.vocab_size:
            raise ValueError(f"vocab_held {self.vocab_held} is not a range "
                             f"of the {self.vocab_size} rows")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if not 0 <= self.first_dense <= self.n_layer:
            raise ValueError("first_dense must lie within the layers")

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_vocab_held(self) -> int:
        return self.vocab_held[1] - self.vocab_held[0]

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return ("latent",) * self.n_layer

    @property
    def mlp_types(self) -> Tuple[str, ...]:
        return tuple("dense" if i < self.first_dense else "sparse"
                     for i in range(self.n_layer))

    @property
    def n_sparse(self) -> int:
        return self.n_layer - self.first_dense

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """The numbers one cache position of one layer holds."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_row(self) -> int:
        """The pool's row: ``cache_row`` padded to whole lanes."""
        return -(-self.cache_row // LANES) * LANES

    # -- YaRN: frequencies and scales, worked out once on the host ----------
    def _mscale(self, m: float) -> float:
        if self.rope_factor <= 1.0:
            return 1.0
        return 0.1 * m * math.log(self.rope_factor) + 1.0

    @property
    def yarn_range(self) -> Tuple[int, int]:
        """``(low, high)``: the frequency indices between which the
        blend ramps, from ``beta_fast`` and ``beta_slow`` rotations
        within the original context."""
        dim = self.qk_rope_head_dim

        def correction(rotations):
            return (dim * math.log(self.rope_original_len
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(self.rope_theta)))

        low = math.floor(correction(self.rope_beta_fast))
        high = math.ceil(correction(self.rope_beta_slow))
        return max(low, 0), min(high, dim - 1)

    @property
    def rope_inv_freq(self) -> np.ndarray:
        """``dr / 2`` frequencies: ``f_i / factor`` where a frequency
        turns less than ``beta_slow`` times in the original context,
        ``f_i`` where it turns more than ``beta_fast`` times, a linear
        blend between."""
        dim = self.qk_rope_head_dim
        f = self.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        low, high = self.yarn_range
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        return ((f / self.rope_factor) * ramp
                + f * (1.0 - ramp)).astype(np.float32)

    @property
    def rope_attention_factor(self) -> float:
        return (self._mscale(self.rope_mscale)
                / self._mscale(self.rope_mscale_all_dim))

    @property
    def softmax_scale(self) -> float:
        """``dq^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) +
        1`` (0.135234 at the published sizes)."""
        m = self._mscale(self.rope_mscale_all_dim)
        return self.q_head_dim ** -0.5 * m * m


def sarvam_mla_tiny(**over) -> SarvamMLAConfig:
    """The CPU tests' preset: the same kinds (layer 0 dense, three
    sparse layers of 16 experts top-4, a latent wider than the rotary
    key), an original context of 16 positions stretched 8 times, every
    width tiny."""
    base = dict(vocab_size=256, d_model=32, n_layer=4, n_head=4,
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
                v_head_dim=8, d_ff=64, d_expert=16, n_experts=16, top_k=4,
                rope_factor=8.0, rope_original_len=16, seq_len=128,
                param_dtype="float32")
    base.update(over)
    return SarvamMLAConfig(**base)


# ---------------------------------------------------------------------------
# the latent mixer, each form written once
# ---------------------------------------------------------------------------

def up_projections(cfg: SarvamMLAConfig, p) -> Tuple[jax.Array, jax.Array]:
    """``Wkvb`` per head: ``W_uk (H, dn, r)`` and ``W_uv (H, r, dv)``.
    A served tree holds them (``prepare_params`` rearranged ``wkvb``
    once); a tree as ``init_params`` made it is rearranged here."""
    if "w_uk" in p:
        return p["w_uk"], p["w_uv"]
    dn = cfg.qk_nope_head_dim
    w = p["wkvb"].reshape(cfg.kv_lora_rank, cfg.n_head, dn + cfg.v_head_dim)
    return (jnp.transpose(w[..., :dn], (1, 2, 0)),
            jnp.transpose(w[..., dn:], (1, 0, 2)))


def latent_projections(cfg: SarvamMLAConfig, p, h: jax.Array,
                       positions: jax.Array):
    """h ``(..., S, d)`` (the block's normed input) -> ``q_n (..., S, H,
    dn)``, ``q_r (..., S, H, dr)`` rotated, and the cache rows ``(...,
    S, r + dr)``: ``[c | k_r]``, the latent after its norm and the one
    shared rotary key after its rotation.

    The query is ``h Wq`` normed per head, or, with a ``q_lora_rank``,
    ``RMSNorm(h W_qa) W_qb`` (``q_a_layernorm``, no norm per head).  With
    ``rope_interleave`` the rotary columns rotate in adjacent pairs: the
    even columns then the odd ones, rotated in halves, as they are
    cached."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    lead = h.shape[:-1]
    if cfg.q_lora_rank:
        q = _mm(rms_norm(_mm(h, p["wqa"]), p["q_a_norm"], cfg.rms_eps),
                p["wqb"]).reshape(lead + (cfg.n_head, cfg.q_head_dim))
    else:
        q = _mm(h, p["wq"]).reshape(lead + (cfg.n_head, cfg.q_head_dim))
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
    ckr = _mm(h, p["wkva"])
    c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.rms_eps)
    yarn = dict(inv_freq=cfg.rope_inv_freq, factor=cfg.rope_attention_factor)

    def rotated(z):
        if cfg.rope_interleave:
            z = jnp.concatenate([z[..., 0::2], z[..., 1::2]], axis=-1)
        return rope(z, positions, cfg.rope_theta, **yarn)

    q_r = rotated(q[..., dn:])
    k_r = rotated(ckr[..., None, r:])
    return q[..., :dn], q_r, jnp.concatenate([c, k_r[..., 0, :]], axis=-1)


def attend_sequence(cfg: SarvamMLAConfig, p, q_n, q_r, rows,
                    attn_impl: str) -> jax.Array:
    """Causal attention over whole sequences, un-absorbed: per-head keys
    and values made from the latent.  q_n ``(B, T, H, dn)``, q_r ``(B,
    T, H, dr)``, rows ``(B, T, r + dr)`` -> ``(B, T, H*dv)``.  No ``T x
    T`` tensor at serving sizes: the flash kernel at head width ``dn +
    dr`` with values of width ``dv``."""
    B, T, H, _ = q_n.shape
    r = cfg.kv_lora_rank
    w_uk, w_uv = up_projections(cfg, p)
    c, k_r = rows[..., :r], rows[..., r:]
    k_n = jnp.einsum("btr,hnr->bthn", c, w_uk,
                     preferred_element_type=jnp.float32).astype(c.dtype)
    v = jnp.einsum("btr,hrv->bthv", c, w_uv,
                   preferred_element_type=jnp.float32).astype(c.dtype)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, :, None], (B, T, H, k_r.shape[-1]))],
        axis=-1)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    if attn_impl == "flash":
        from ray_lightning_tpu.ops.flash_attention import flash_attention

        out = flash_attention(q, k, v, scale=cfg.softmax_scale)
        return out.reshape(B, T, H * cfg.v_head_dim)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   preferred_element_type=jnp.float32) * cfg.softmax_scale
    vis = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(vis, s, _NEG_INF), axis=-1)
    out = jnp.einsum("bhqs,bshv->bqhv", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H * cfg.v_head_dim).astype(q.dtype)


def absorbed_queries(cfg: SarvamMLAConfig, p, q_n, q_r) -> jax.Array:
    """``[q_n W_uk^T | q_r | 0]``: ``(W, H, pool_row)``, a head's query
    against the cache row as it lies."""
    w_uk, _ = up_projections(cfg, p)
    q_lat = jnp.einsum("whn,hnr->whr", q_n, w_uk,
                       preferred_element_type=jnp.float32).astype(q_n.dtype)
    return _pad_row(cfg, jnp.concatenate([q_lat, q_r], axis=-1))


def _pad_row(cfg: SarvamMLAConfig, rows: jax.Array) -> jax.Array:
    pad = cfg.pool_row - cfg.cache_row
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])


def sequence_forward(cfg: SarvamMLAConfig, params, tokens: jax.Array,
                     row_valid: Optional[jax.Array] = None,
                     attn_impl: str = "auto", moe_impl: str = "auto",
                     routing: Optional[list] = None):
    """The trunk over whole sequences: tokens ``(B, T)`` (ids within the
    held vocabulary slice) -> ``(hidden (B, T, d) before the final norm,
    per-layer cache rows (B, T, r + dr), counts int32[2])``."""
    B, T = tokens.shape
    attn_impl = _resolve_attn(attn_impl, T)
    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    valid = None if row_valid is None else row_valid.reshape(B * T)
    cached: List[jax.Array] = []
    counts = jnp.zeros((2,), jnp.int32)
    for p, mlp in zip(params["layers"], cfg.mlp_types):
        def mixer(h, p=p):
            q_n, q_r, rows = latent_projections(cfg, p, h, positions)
            cached.append(rows)
            return _mm(attend_sequence(cfg, p, q_n, q_r, rows, attn_impl),
                       p["wo"])

        x, c = decoder_block(cfg, p, x, mixer, mlp, valid, moe_impl, routing)
        counts = counts + c
    return x, cached, counts


# ---------------------------------------------------------------------------
# the serving cache: one kind of state, one row a position
# ---------------------------------------------------------------------------

class LatentKVCache:
    """One pool tensor ``kv (L, N, Bs, pool_row)`` holding ``[c | k_r |
    0]``, one block table a slot, one allocator.  The padding lanes are
    written as zeros and meet zeros in the query: never read as data."""

    def __init__(self, cfg: SarvamMLAConfig, num_blocks: int,
                 block_size: int, dtype=jnp.bfloat16):
        from ray_lightning_tpu.serve.kv_cache import BlockAllocator

        self.cfg = cfg
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.dtype = dtype
        self.allocator = BlockAllocator(num_blocks)

    def init_pool(self) -> Dict[str, jax.Array]:
        return {"kv": jnp.zeros(
            (self.cfg.n_layer, self.num_blocks, self.block_size,
             self.cfg.pool_row), self.dtype)}

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def export_blocks(self, pool, ids):
        raise ValueError(
            f"export_blocks is not supported for the {FAMILY} family: "
            f"{ServeFamily.refuses_why}")


def paged_prefill(cfg: SarvamMLAConfig, params, pool, tokens, prompt_len,
                  block_ids, compute_dtype=None, attn_impl: str = "auto",
                  moe_impl: str = "auto", **unused):
    """One prompt (``tokens (T,)`` right-padded to a bucket) through
    :func:`sequence_forward`, un-absorbed; its latent rows written into
    the slot's blocks ``block_ids (T // Bs,)``.  Returns ``(logits
    (V_held,) float32 at position prompt_len - 1, pool, counts)``."""
    T = tokens.shape[0]
    Bs = pool["kv"].shape[2]
    valid = jnp.arange(T) < prompt_len
    x, cached, counts = sequence_forward(
        cfg, params, tokens[None], row_valid=valid[None],
        attn_impl=attn_impl, moe_impl=moe_impl)
    h_last = jax.lax.dynamic_index_in_dim(
        x[0], prompt_len - 1, axis=0, keepdims=False)
    logits = head_logits(cfg, params, h_last)
    rows = _pad_row(cfg, jnp.stack([c[0] for c in cached]))
    kv = pool["kv"].at[:, block_ids].set(
        rows.astype(pool["kv"].dtype).reshape(
            cfg.n_layer, T // Bs, Bs, cfg.pool_row))
    return logits, {"kv": kv}, counts


def decode_latent(cfg: SarvamMLAConfig, p, h, pos, kv, layer,
                  block_tables, attn_impl: str):
    """One latent layer's decode attention, absorbed: h ``(W, d)`` (the
    block's normed input) at positions ``pos (W,)`` against pool layer
    ``layer`` of ``kv`` by ``rlt_mla_decode`` (``attn_impl`` "pallas") or
    its XLA gather.  Returns ``(a (W, H*dv) in h's dtype, before the
    output projection; the position's cache row (W, pool_row) to be
    written)``."""
    from ray_lightning_tpu.ops.paged_attention import mla_decode_attention

    q_n, q_r, rows = latent_projections(cfg, p, h[:, None], pos[:, None])
    row = _pad_row(cfg, rows[:, 0]).astype(kv.dtype)
    att = mla_decode_attention(
        absorbed_queries(cfg, p, q_n[:, 0], q_r[:, 0]), row, kv, layer,
        block_tables, pos, rank=cfg.kv_lora_rank, scale=cfg.softmax_scale,
        impl=attn_impl)
    _, w_uv = up_projections(cfg, p)
    out = jnp.einsum("whr,hrv->whv", att.astype(h.dtype), w_uv,
                     preferred_element_type=jnp.float32)
    return out.astype(h.dtype).reshape(h.shape[0], -1), row


def paged_decode_step(cfg: SarvamMLAConfig, params, pool, block_tables,
                      seq_lens, tokens, compute_dtype=None,
                      attn_impl: str = "auto", moe_impl: str = "auto",
                      **unused):
    """One token for every slot, absorbed.  ``block_tables (W, M)``;
    ``seq_lens (W,)`` the positions already cached (0 = an idle slot,
    taken out of the routing).  Returns ``(logits (W, V_held) float32,
    pool, counts)``.

    Each layer's ``rlt_mla_decode`` walks the slot's resident blocks
    where they lie (``auto`` on a TPU; the XLA gather of the same
    arithmetic elsewhere).  The pool is only read inside the layer
    loop; every layer's new row is scattered into it afterwards, in one
    scatter."""
    kv = pool["kv"]
    attn_impl = latent_impl(cfg, attn_impl, kv)
    W, M = block_tables.shape
    Bs = kv.shape[2]
    pos = seq_lens
    active = seq_lens > 0
    x = params["embed"][tokens]
    new: List[jax.Array] = []
    counts = jnp.zeros((2,), jnp.int32)
    for i, (p, mlp) in enumerate(zip(params["layers"], cfg.mlp_types)):
        def mixer(h, p=p, i=i):
            a, row = decode_latent(cfg, p, h, pos, kv, jnp.int32(i),
                                   block_tables, attn_impl)
            new.append(row)
            return _mm(a, p["wo"])

        x, c = decoder_block(cfg, p, x, mixer, mlp, active, moe_impl)
        counts = counts + c
    logits = head_logits(cfg, params, x)
    # Every index explicit, one row a slot a layer (PERF.md, PR 25: a
    # slice over the layer axis makes XLA re-lay the pool).
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(pos // Bs, M - 1)[:, None], axis=1)[:, 0]
    layer = jnp.arange(cfg.n_layer, dtype=jnp.int32)
    kv = kv.at[(layer[:, None], blk[None, :], (pos % Bs)[None, :])].set(
        jnp.stack(new))
    return logits, {"kv": kv}, counts


def latent_impl(cfg: SarvamMLAConfig, attn_impl: str, kv) -> str:
    """The latent decode's path on the pool ``kv``: ``auto`` takes
    ``rlt_mla_decode`` where it tiles the pool (a TPU), else the XLA
    gather; ``pallas`` / ``xla`` as asked."""
    from ray_lightning_tpu.ops.paged_attention import mla_decode_supported

    if attn_impl != "auto":
        return attn_impl
    return ("pallas" if mla_decode_supported(kv, cfg.n_head, cfg.kv_lora_rank)
            else "xla")


class ServeFamily:
    """What :class:`~ray_lightning_tpu.serve.engine.ServeEngine` asks a
    module for (``serve/kv_cache.py`` ``GPTServeFamily`` is the same
    seam): one kind of cache state, so ``two_kind`` is False and the
    scheduler's and the engine's tables are GPT's."""

    name = FAMILY
    two_kind = False
    refuses = ExaoneServeFamily.refuses     # the same six, for another reason
    refuses_why = (
        "a sequence's state is latent rows that only this family's "
        "prefill and decode programs read and write (the chunked, "
        "verify, adapter and block-transfer programs are GPT's)")

    def __init__(self, module: "SarvamMLA"):
        self.cfg = cfg = module.config
        self.vocab_size = cfg.n_vocab_held
        self.n_sparse = cfg.n_sparse
        self.n_latent = cfg.n_layer
        self.attn_impl = module.attn_impl
        kw = dict(attn_impl=module.attn_impl, moe_impl=module.moe_impl)
        self.prefill = functools.partial(paged_prefill, cfg, **kw)
        self.decode = functools.partial(paged_decode_step, cfg, **kw)

    def walk_layers(self, pool) -> int:
        """Layers whose decode attention runs ``rlt_mla_decode`` on
        ``pool``: all where the decode takes the kernel, else none."""
        kernel = latent_impl(self.cfg, self.attn_impl, pool["kv"])
        return self.cfg.n_layer if kernel == "pallas" else 0

    def latent_row_bytes(self, dtype) -> int:
        """The bytes of data one cached position of one layer holds
        (the padding lanes are not data)."""
        return self.cfg.cache_row * jnp.dtype(dtype).itemsize

    def make_cache(self, num_blocks: int, block_size: int, num_slots: int,
                   dtype) -> LatentKVCache:
        return LatentKVCache(self.cfg, num_blocks, block_size, dtype)

    def prepare_params(self, tree: Dict[str, Any],
                       compute_dtype) -> Dict[str, Any]:
        """The tree as it came (``init_params`` makes the weights in the
        compute dtype), but for ``wkvb``: each layer's is rearranged
        once into ``w_uk (H, dn, r)`` and ``w_uv (H, r, dv)``, the
        operands decode's absorbed form and prefill's expansion both
        read, and is not kept beside them."""
        split = jax.jit(functools.partial(up_projections, self.cfg))
        layers = []
        for p in tree["layers"]:
            if "wkvb" in p:
                w_uk, w_uv = split({"wkvb": p["wkvb"]})
                p = {k: v for k, v in p.items() if k != "wkvb"}
                p.update(w_uk=w_uk, w_uv=w_uv)
            layers.append(p)
        return {**tree, "layers": layers}


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------

class SarvamMLA(ExaoneMoE):
    """``TpuModule`` of the family: ``ExaoneMoE``'s (its ``attn_impl``
    and ``moe_impl``, its loss and optimizer) with this family's
    weights, trunk and serving programs."""

    # The selection bias is a buffer of the published model, non-zero
    # after training; drawn from the seed so that the chosen set and
    # the gates' weights differ in a run.
    ROUTER_BIAS_STD = 0.01
    _sequence_forward = staticmethod(sequence_forward)

    def serve_family(self) -> ServeFamily:
        return ServeFamily(self)

    def init_params(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        d, H, r = cfg.d_model, cfg.n_head, cfg.kv_lora_rank

        def mixer_leaves(w, ks, kind):
            del kind
            return {
                "wq": w(ks[0], (d, H * cfg.q_head_dim)),
                "wkva": w(ks[1], (d, cfg.cache_row)),
                "wkvb": w(ks[2], (r, H * (cfg.qk_nope_head_dim
                                          + cfg.v_head_dim))),
                "wo": w(ks[3], (H * cfg.v_head_dim, d)),
                "q_norm": jnp.ones((cfg.q_head_dim,), jnp.float32),
                "kv_norm": jnp.ones((r,), jnp.float32),
                "attn_norm": jnp.ones((d,), jnp.float32),
                "ffn_norm": jnp.ones((d,), jnp.float32),
            }

        return init_tree(cfg, rng, mixer_leaves,
                         router_bias_std=self.ROUTER_BIAS_STD)
