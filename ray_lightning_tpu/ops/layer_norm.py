"""Fused LayerNorm: one HBM pass per direction instead of XLA's stats +
normalize chains.

LayerNorm is pure bandwidth: per call the residual stream is read for
the mean/var pass and again for the normalization, plus f32 temporaries
— at GPT-2-small shapes the 25 LN sites cost ~7 ms of a ~172 ms step
(docs/PERFORMANCE.md "where the remaining time goes").  The Pallas
kernels read each (block_t, d) tile once, keep the f32 statistics in
registers, and write the output once; the backward recomputes x̂ from
the saved per-row (mu, rstd) — d-sized reductions stay in-tile, and the
cross-token dgamma/dbeta reductions accumulate in one resident (1, d)
output block across the token grid.  The no-grad (eval) primal compiles
a y-only kernel: no statistics are written at all.

Dispatch mirrors :mod:`.cross_entropy`: callers opt in on single-chip
paths (``pallas_call`` is opaque to the GSPMD partitioner) and shapes
must be lane-aligned; anything else runs the plain XLA math — which is
also the exact reference-numerics path (f32 stats, tested parity 1e-6).
Which path a call takes is a function of its shapes and
``RLT_DISABLE_KERNELS`` only: a kernel the compiler refuses is an error
(``tests/test_chip_compile.py`` compiles these for the chip).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_lightning_tpu.ops.kernel_probe import (
    _interpret,
    kernel_family_disabled,
)

__all__ = ["layer_norm"]

_LN_BLOCK_T = 512
# Saved-statistic lane width: 8 (one sublane), the flash-attention lse
# pattern — wide enough for Mosaic tiling, 16x less residual memory
# than a full 128-lane broadcast.
_STAT_W = 8
_LANE = 128
_EPS = 1e-5


def _xla_layer_norm(x, g, b):
    """Reference math (identical to the historical models/gpt.py inline
    implementation — numerics are frozen by parity tests)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + _EPS)
    return (y * g + b).astype(x.dtype)


def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref=None, rs_ref=None):
    """Forward tile; ``mu_ref``/``rs_ref`` absent = y-only (eval) mode."""
    x = x_ref[...].astype(jnp.float32)                  # (bt, d)
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rs = jax.lax.rsqrt(var + _EPS)
    y = xc * rs * g_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32
    )
    y_ref[...] = y.astype(y_ref.dtype)
    if mu_ref is not None:
        mu_ref[...] = jnp.broadcast_to(mu, mu_ref.shape)
        rs_ref[...] = jnp.broadcast_to(rs, rs_ref.shape)


def _ln_bwd_kernel(x_ref, g_ref, dy_ref, mu_ref, rs_ref, dx_ref, dg_ref,
                   db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mu = mu_ref[:, :1]
    rs = rs_ref[:, :1]
    xhat = (x - mu) * rs
    dyg = dy * g_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dyg, axis=1, keepdims=True)
    m2 = jnp.mean(dyg * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((dyg - m1 - xhat * m2) * rs).astype(dx_ref.dtype)
    # Cross-token reductions: every grid step maps dg/db to the same
    # (1, d) block, so it stays resident in VMEM and accumulates (a
    # (1, d) block per step on an (nb, d) array is not a legal TPU
    # tile: the sublane dim must be 8-aligned or the whole array).
    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _pad_tokens(x2, n):
    n_pad = -(-n // _LN_BLOCK_T) * _LN_BLOCK_T
    if n_pad != n:
        pad_shape = (n_pad - n,) + x2.shape[1:]
        x2 = jnp.concatenate(
            [x2, jnp.zeros(pad_shape, x2.dtype)], axis=0
        )
    return x2, n_pad


def _ln_fwd_pallas(x, g, b, want_stats):
    """Returns ``y`` (x's shape/dtype) and, when ``want_stats``, PADDED
    ``(n_pad, _STAT_W)`` f32 (mu, rstd) ready for the backward."""
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    x2, n_pad = _pad_tokens(x2, n)
    bt = _LN_BLOCK_T
    row_spec = pl.BlockSpec((bt, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((bt, _STAT_W), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n_pad, d), x.dtype)
    stat_shape = jax.ShapeDtypeStruct((n_pad, _STAT_W), jnp.float32)
    result = pl.pallas_call(
        _ln_fwd_kernel,
        out_shape=(out_shape, stat_shape, stat_shape)
        if want_stats else out_shape,
        grid=(n_pad // bt,),
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=(row_spec, stat_spec, stat_spec)
        if want_stats else row_spec,
        interpret=_interpret(),
        name="rlt_ln_fwd",
    )(x2, g.reshape(1, d), b.reshape(1, d))
    if want_stats:
        y, mu, rs = result
        return y[:n].reshape(shape), mu, rs
    return result[:n].reshape(shape), None, None


def _ln_bwd_pallas(x, g, dy, mu_pad, rs_pad):
    shape = x.shape
    d = shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    x2, n_pad = _pad_tokens(x2, n)
    # dy stays in its native dtype — the kernel casts per tile; padded
    # rows carry zero cotangent so they contribute nothing.
    dy2, _ = _pad_tokens(dy.reshape(-1, d), n)
    bt = _LN_BLOCK_T
    nb = n_pad // bt
    row_spec = pl.BlockSpec((bt, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((bt, _STAT_W), lambda i: (i, 0))
    dx, dg, db = pl.pallas_call(
        _ln_bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((n_pad, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ),
        grid=(nb,),
        in_specs=[row_spec, vec_spec, row_spec, stat_spec, stat_spec],
        out_specs=(row_spec, vec_spec, vec_spec),
        interpret=_interpret(),
        name="rlt_ln_bwd",
    )(x2, g.reshape(1, d), dy2, mu_pad, rs_pad)
    return dx[:n].reshape(shape), dg[0], db[0]


@jax.custom_vjp
def _fused_ln(x, g, b):
    y, _, _ = _ln_fwd_pallas(x, g, b, want_stats=False)
    return y


def _fused_ln_fwd(x, g, b):
    y, mu, rs = _ln_fwd_pallas(x, g, b, want_stats=True)
    return y, (x, g, mu, rs)


def _fused_ln_bwd(res, dy):
    x, g, mu, rs = res
    dx, dg, db = _ln_bwd_pallas(x, g, dy, mu, rs)
    return dx, dg.astype(g.dtype), db.astype(g.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def _kernel_selected(d: int, use_pallas: bool) -> bool:
    """The fused path's whole gate: opted in, lane-aligned, not
    switched off."""
    return (bool(use_pallas) and d % _LANE == 0
            and not kernel_family_disabled("ln"))


def layer_norm(x, g, b, use_pallas: bool = False):
    """LayerNorm over the last dim; f32 statistics, output in ``x.dtype``.

    ``use_pallas=True`` opts into the fused kernels on lane-aligned
    shapes (single-chip / explicit-SPMD callers only — the kernel is
    opaque to the GSPMD partitioner); anything else runs the identical
    XLA math.
    """
    if _kernel_selected(x.shape[-1], use_pallas):
        return _fused_ln(x, g, b)
    return _xla_layer_norm(x, g, b)
