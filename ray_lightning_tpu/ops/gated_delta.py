"""The gated delta rule (Gated DeltaNet's recurrence) over heads whose
whole memory of a sequence is one float32 state a value head.

For a value head, with ``S`` of shape ``(K, V)`` (keys down, values
across: the layout of the published chunked and recurrent kernels) and
at each position ``t`` a query ``q_t`` and key ``k_t`` (L2-normalised,
the query scaled by ``K^-0.5``), a value ``v_t``, a decay ``exp(g_t)``
and a write strength ``beta_t``::

    S   = exp(g_t) S_{t-1}
    S   = S + k_t (beta_t (v_t - S^T k_t))^T        the delta rule
    o_t = S^T q_t

Two forms, each written once here:

* :func:`chunked` (prefill): chunks of ``CHUNK`` positions; inside a
  chunk the writes' mutual corrections are one unit lower-triangular
  solve, across chunks the state is carried by a ``lax.scan``.  Every
  product at ``Precision.HIGHEST``: the state is an accumulator.
* :func:`decode_step` (one position a slot): the kernel
  ``rlt_gdn_decode`` reads each active slot's state once into VMEM,
  decays it, applies the rank-1 correction, writes it back in place (the
  pool is aliased) and gives ``S^T q``; an idle slot (``seq_len`` 0)
  costs no state traffic and keeps its state.  The XLA path computes
  the same arithmetic (the CPU tests', and what the kernel is timed
  against).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.kernel_probe import (
    _interpret, kernel_family_disabled,
)

__all__ = ["CHUNK", "l2norm", "chunked", "decode_step", "decode_supported"]

CHUNK = 64                  # positions a chunk of the prefill's scan
_HIGHEST = jax.lax.Precision.HIGHEST
# Value heads a kernel step takes: 32 states of 64 KiB, in and out and
# double-buffered, are 8 MiB of VMEM, under the compiler's default.
_HEADS_PER_STEP = 32


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _ein(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HIGHEST)


def chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The recurrence over whole sequences, a chunk at a time.

    q, k ``(B, T, H, K)``; v ``(B, T, H, V)``; g, beta ``(B, T, H)``;
    state ``(B, H, K, V)``; all float32.  A position with ``beta = 0``
    and ``g = 0`` leaves the state as it was (a prompt's padding).
    Returns ``(o (B, T, H, V), the state after position T - 1)``."""
    B, T, H, K = k.shape
    pad = -T % chunk
    if pad:
        def padded(z):
            return jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))

        q, k, v, g, beta = map(padded, (q, k, v, g, beta))
    n = (T + pad) // chunk

    def split(z):       # (B, T, H, ...) -> (n, B, H, C, ...)
        z = z.reshape((B, n, chunk, H) + z.shape[3:])
        return jnp.moveaxis(z, (1, 3), (0, 2))

    qc, kc, vc, bc = split(q), split(k), split(v), split(beta)
    gc = jnp.cumsum(split(g), axis=-1)              # (n, B, H, C)
    i = jnp.arange(chunk)[:, None]
    j = jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(i >= j, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))            # (n, B, H, C, C)
    kb = kc * bc[..., None]
    # Within a chunk each write corrects for the earlier ones: the unit
    # lower-triangular system (I + A) X = Y, A_ij = beta_i k_i.k_j
    # exp(g_i - g_j) for j < i.
    a = jnp.where(i > j, _ein("...ik,...jk->...ij", kb, kc) * decay, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    tri = jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True,
        unit_diagonal=True)
    u = _ein("...ij,...jv->...iv", tri, vc * bc[..., None])
    w = _ein("...ij,...jk->...ik", tri, kb * jnp.exp(gc)[..., None])
    intra = _ein("...ik,...jk->...ij", qc, kc) * decay

    def step(s, xs):
        q_i, k_i, u_i, w_i, g_i, a_i = xs
        v_new = u_i - _ein("...ck,...kv->...cv", w_i, s)
        o = (_ein("...ck,...kv->...cv", q_i * jnp.exp(g_i)[..., None], s)
             + _ein("...ij,...jv->...iv", a_i, v_new))
        last = g_i[..., -1:]
        s = (s * jnp.exp(last)[..., None]
             + _ein("...ck,...cv->...kv",
                    k_i * jnp.exp(last - g_i)[..., None], v_new))
        return s, o

    state, o = jax.lax.scan(step, state, (qc, kc, u, w, gc, intra))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, n * chunk, H, -1)
    return o[:, :T], state


def _step_xla(s, q, k, v, g, beta):
    """One position of the recurrence: s ``(W, H, K, V)``, q / k ``(W,
    H, K)``, v ``(W, H, V)``, g / beta ``(W, H)`` -> ``(o, s)``."""
    s = s * jnp.exp(g)[..., None, None]
    mem = _ein("whkv,whk->whv", s, k)
    s = s + k[..., :, None] * ((v - mem) * beta[..., None])[..., None, :]
    return _ein("whkv,whk->whv", s, q), s


def decode_supported(state_pool: jax.Array, n_key_heads: int) -> bool:
    """Whether ``impl="auto"`` takes ``rlt_gdn_decode`` for a pool
    ``(L, slots, H, K, V)``: a TPU, float32 states of 128 x 128, whole
    kernel steps of value heads over whole key heads, and the ``gdn``
    kernel family not switched off (``RLT_DISABLE_KERNELS``)."""
    _, _, H, K, V = state_pool.shape
    return (jax.default_backend() == "tpu"
            and not kernel_family_disabled("gdn")
            and state_pool.dtype == jnp.float32 and K == V == 128
            and H % _HEADS_PER_STEP == 0 and H % n_key_heads == 0
            and _HEADS_PER_STEP % (H // n_key_heads) == 0)


def _kernel(layer_ref, lens_ref, src_ref, blk_ref, s_ref, kq_ref, v_ref,
            d_ref, b_ref, o_ref, s_out, *, heads, group, n_key):
    w = pl.program_id(0)
    live = lens_ref[w] > 0

    @pl.when(live)
    def _():
        kq = kq_ref[...]                    # (K, 2 n_key): keys, queries
        for h in range(heads):
            c = h // group
            kcol, qcol = kq[:, c:c + 1], kq[:, n_key + c:n_key + c + 1]
            s = s_ref[h] * d_ref[h:h + 1, :]
            mem = jnp.sum(s * kcol, axis=0, keepdims=True)      # (1, V)
            s = s + kcol * (b_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - mem))
            s_out[h] = s
            o_ref[h:h + 1, :] = jnp.sum(s * qcol, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # An idle slot's steps name a live slot's block (:func:`_visits`):
    # nothing is read or written for them.  Only where no slot is live
    # does each step name its own block, and carries it through.
    @pl.when(jnp.logical_not(live) & (src_ref[w] == w))
    def _():
        s_out[...] = s_ref[...]


def _visits(seq_lens, nb: int):
    """The state blocks the kernel's steps ``(w, j)`` name: ``(slot (W,),
    block (W * nb,))``.  A live slot's steps name its own blocks; an idle
    slot's steps name the block the step before them named (the last
    block of the nearest live slot before it, else the first of the
    nearest after it), so that the pipeline neither fetches nor writes
    back anything for them.  With no live slot every step names its
    own."""
    W = seq_lens.shape[0]
    live = seq_lens > 0
    idx = jnp.arange(W, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    after = jax.lax.cummin(jnp.where(live, idx, W), reverse=True)
    src = jnp.where(live, idx, jnp.where(before >= 0, before,
                                         jnp.where(after < W, after, idx)))
    own = live | (src == idx)
    blk = jnp.where(own[:, None], jnp.arange(nb, dtype=jnp.int32)[None],
                    jnp.where(before >= 0, nb - 1, 0)[:, None])
    return src.astype(jnp.int32), blk.reshape(-1).astype(jnp.int32)


def decode_step(state_pool, layer, q, k, v, g, beta, seq_lens,
                impl: str = "auto"):
    """One position for every slot of pool layer ``layer``.

    state_pool ``(L, W, H, K, V)`` float32; q, k ``(W, Hk, K)`` (value
    head ``h`` reads key head ``h // (H / Hk)``); v ``(W, H, V)``; g,
    beta ``(W, H)``; all float32; seq_lens ``(W,)`` (0: an idle slot,
    whose state is left as it is).  Returns ``(o (W, H, V) float32,
    state_pool)``: under ``"pallas"`` the pool is updated in place
    (``rlt_gdn_decode``), under ``"xla"`` by one update of the layer."""
    L, W, H, K, V = state_pool.shape
    hk = q.shape[1]
    group = H // hk
    if impl == "auto":
        impl = "pallas" if decode_supported(state_pool, hk) else "xla"
    if impl == "xla":
        old = state_pool[layer]
        o, s = _step_xla(old, jnp.repeat(q, group, axis=1),
                         jnp.repeat(k, group, axis=1), v, g, beta)
        live = (seq_lens > 0)[:, None, None, None]
        return (jnp.where(live[..., 0], o, 0.0),
                state_pool.at[layer].set(jnp.where(live, s, old)))
    if impl != "pallas":
        raise ValueError(f"Unknown gated delta decode impl {impl!r} "
                         "(auto|xla|pallas)")
    hb = _HEADS_PER_STEP
    nb = H // hb
    kb = hb // group                        # key heads a step reads
    # A step's keys and queries as columns: (W, nb, K, 2 kb).
    kq = jnp.concatenate([k.reshape(W, nb, kb, K), q.reshape(W, nb, kb, K)],
                         axis=2)
    kq = jnp.swapaxes(kq, 2, 3)
    kernel = functools.partial(_kernel, heads=hb, group=group, n_key=kb)
    by_head = pl.BlockSpec((None, hb, V), lambda w, j, *_: (w, j, 0))

    state = pl.BlockSpec(
        (None, None, hb, K, V),
        lambda w, j, layer, lens, src, blk: (layer[0], src[w],
                                             blk[w * nb + j], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(W, nb),
        in_specs=[
            state,
            pl.BlockSpec((None, None, K, 2 * kb),
                         lambda w, j, *_: (w, j, 0, 0)),
            by_head, by_head, by_head,
        ],
        out_specs=[by_head, state],
    )
    o, state_pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((W, H, V), jnp.float32),
                   jax.ShapeDtypeStruct(state_pool.shape, jnp.float32)],
        # The pool (operand 4, after the prefetched scalars) is the
        # second output: written in place.
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="rlt_gdn_decode",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        seq_lens.astype(jnp.int32),
        *_visits(seq_lens, nb),
        # A head's decay and strength as rows of V lanes (Mosaic does not
        # broadcast one number over a state in one step).
        state_pool, kq, v, *(jnp.broadcast_to(z[..., None], (W, H, V))
                             for z in (jnp.exp(g), beta)),
    )
    return o, state_pool
