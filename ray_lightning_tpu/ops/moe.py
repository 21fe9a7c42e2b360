"""Mixture-of-Experts routing + expert-parallel MLP (GShard/Switch style).

Net-new over the reference (SURVEY §2.3: "EP (expert parallel / MoE):
absent"), built TPU-first:

* **Dense dispatch, static shapes.** Routing is expressed as einsums
  against one-hot dispatch/combine tensors (the GShard formulation) —
  no gathers/scatters with data-dependent shapes, so XLA tiles
  everything onto the MXU and the program never recompiles.  Capacity
  ``C`` bounds per-expert work; overflow tokens are dropped from the
  expert path (they still flow through the residual).
* **Grouped routing.** Tokens are routed within ``groups`` independent
  groups (GShard's group dim), sized by the caller to the data-parallel
  shard count: dispatch tensors are ``[G, s, E, C]`` with ``s = S/G``
  (linear in S, not quadratic), and the capacity cumsum runs *within*
  a group — shard-local under GSPMD, no cross-shard router state.
* **Expert parallelism as an annotation.** Expert-stacked weights
  ``[E, d, h]`` carry ``P("expert", ...)`` specs; with an ``expert``
  mesh axis, GSPMD turns the dispatch einsum into the all-to-all that
  ships token slots to their expert's device, composing with tensor
  parallelism on the hidden dim.
* **Load balancing** via the Switch-Transformer auxiliary loss,
  normalized so a perfectly uniform assignment scores 1.0 for any
  ``top_k``.

Beside the capacity layer stands the **dropless** one
(:func:`dropless_moe`), the expert layer of sigmoid-scored top-k models
served expert-parallel: the router scores ALL experts in float32
(:func:`sigmoid_topk_routing`), this chip is told which contiguous
range of them it holds, and only assignments that fall on held experts
are computed: rows sorted by expert, then a grouped matmul over the
experts held (:func:`grouped_swiglu`; Pallas kernels ``rlt_moe_gate_up``
and ``rlt_moe_down``).  No capacity, no dropped token, no one-hot
dispatch tensor; what the absent experts would have added is simply not
in the result.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.kernel_probe import (
    _interpret, kernel_family_disabled,
)

__all__ = [
    "topk_capacity_routing", "moe_mlp", "load_balance_loss",
    "sigmoid_topk_routing", "grouped_swiglu", "dropless_moe",
]


def topk_capacity_routing(
    probs: jax.Array, top_k: int, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Greedy top-k assignment with per-expert capacity (one group).

    probs: ``[s, E]`` router probabilities (f32).
    Returns ``(combine, dispatch)``, both ``[s, E, C]``: ``dispatch`` is
    the 0/1 token→(expert, slot) assignment; ``combine`` additionally
    carries the (renormalized) gate weight of each assignment.
    """
    s, E = probs.shape
    top_k = min(top_k, E)  # k > E would re-route masked tokens to expert 0
    remaining = probs
    slots_used = jnp.zeros((1, E), jnp.float32)
    dispatch = jnp.zeros((s, E, capacity), jnp.float32)
    combine = jnp.zeros((s, E, capacity), jnp.float32)
    for _ in range(top_k):  # static, small
        choice = jnp.argmax(remaining, axis=-1)                   # [s]
        onehot = jax.nn.one_hot(choice, E, dtype=jnp.float32)     # [s, E]
        # Queue position of each token within its chosen expert, offset
        # by slots already consumed in earlier rounds.
        position = jnp.cumsum(onehot, axis=0) - onehot + slots_used
        fits = (position < capacity) * onehot                     # [s, E]
        slot = jax.nn.one_hot(
            position.astype(jnp.int32), capacity, dtype=jnp.float32
        )                                                         # [s, E, C]
        d = slot * fits[..., None]
        gate = (probs * onehot).sum(-1)                           # [s]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        slots_used = slots_used + fits.sum(0, keepdims=True)
        remaining = remaining * (1.0 - onehot)
    # Normalize gates over the (≤ top_k) experts that accepted the token.
    denom = combine.sum(axis=(1, 2), keepdims=True)
    combine = combine / jnp.maximum(denom, 1e-9)
    return combine, dispatch


def load_balance_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch-Transformer aux loss: ``E · Σ_e f_e · p̄_e``.

    ``f_e`` = fraction of *dispatches* landing on expert e (normalized by
    the total dispatch count, so the result is 1.0 for a uniform
    assignment regardless of ``top_k``), ``p̄_e`` = mean router
    probability.
    """
    E = probs.shape[-1]
    per_expert = dispatch.sum(axis=(0, 2))                        # [E]
    frac = per_expert / jnp.maximum(per_expert.sum(), 1.0)
    mean_prob = probs.mean(axis=0)                                # [E]
    return E * jnp.sum(frac * mean_prob)


def moe_mlp(
    x: jax.Array,
    gate_w: jax.Array,
    w_in: jax.Array,
    b_in: jax.Array,
    w_out: jax.Array,
    b_out: jax.Array,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    groups: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MLP block: route → dispatch → expert FFN → combine.

    x ``[B, T, d]``; gate_w ``[d, E]``; w_in ``[E, d, h]``; b_in
    ``[E, h]``; w_out ``[E, h, d]``; b_out ``[E, d]``.  ``groups`` should
    equal the data-parallel shard count (see module docstring); it is
    clamped to 1 when it does not divide the token count.  Returns
    ``(y [B, T, d], aux_loss scalar)``.  Router math in f32 regardless of
    the compute dtype (gate decisions must not flip with bf16 rounding).
    """
    B, T, d = x.shape
    E = gate_w.shape[-1]
    S = B * T
    G = groups if groups > 0 and S % groups == 0 else 1
    s = S // G
    capacity = max(1, int(math.ceil(s / E * capacity_factor)))
    xg = x.reshape(G, s, d)

    logits = jnp.einsum(
        "gsd,de->gse", xg.astype(jnp.float32), gate_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)                       # [G, s, E]
    combine, dispatch = jax.vmap(
        lambda p: topk_capacity_routing(p, top_k, capacity)
    )(probs)
    # Aux loss over *globally aggregated* statistics, not a per-group
    # mean: E·Σ f_e·p̄_e with f_e and p̄_e formed from the all-group
    # dispatch counts / router probs.  A per-group mean of the loss is
    # mesh-dependent (E[f·p] ≠ E[f]·E[p] across groups), which broke
    # sharded parity; plain sums stay shard-local-friendly under GSPMD.
    aux = load_balance_loss(
        probs.reshape(G * s, E), dispatch.reshape(G * s, E, capacity)
    )

    c = x.dtype
    # Dispatch: the ep all-to-all under GSPMD (token slots → expert shard).
    xd = jnp.einsum("gsec,gsd->gecd", dispatch.astype(c), xg,
                    preferred_element_type=jnp.float32).astype(c)
    h = jax.nn.gelu(
        jnp.einsum("gecd,edh->gech", xd, w_in,
                   preferred_element_type=jnp.float32).astype(c)
        + b_in[None, :, None, :].astype(c)
    )
    yo = (jnp.einsum("gech,ehd->gecd", h, w_out,
                     preferred_element_type=jnp.float32).astype(c)
          + b_out[None, :, None, :].astype(c))
    # Combine: the return all-to-all, weighted by the gates.
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(c), yo,
                   preferred_element_type=jnp.float32)
    return y.reshape(B, T, d).astype(c), aux


# ---------------------------------------------------------------------------
# the dropless layer
# ---------------------------------------------------------------------------

# Rows of one grouped-matmul tile.  A visit multiplies a whole tile
# whatever share of its rows the visited expert owns, so the tile
# follows the rows an expert can expect (decode: a few; prefill:
# hundreds) and never passes the MXU's 128.
_GMM_MAX_TILE = 128
_GMM_MIN_TILE = 16          # one bf16 sublane tile
# Columns of a weight block: (K, tn) bf16, double-buffered, two of them
# in the fused gate/up kernel.
_GMM_BLOCK_BYTES = 6 * 1024 * 1024
_GMM_VMEM_LIMIT = 64 * 1024 * 1024


def sigmoid_topk_routing(
    x: jax.Array, router_w: jax.Array, bias: jax.Array, top_k: int,
    scale: float = 1.0, return_scores: bool = False,
):
    """Sigmoid scores over ALL experts in float32, the ``top_k`` chosen
    by ``score + bias`` (the bias selects, it does not weigh), gates the
    chosen scores normalised over the chosen set and scaled.

    x ``[S, d]``; router_w ``[d, E]``; bias ``[E]``.  Returns
    ``(idx [S, k] int32 global expert ids, gates [S, k] float32)``, and
    the scores ``[S, E]`` after them when ``return_scores``.
    """
    z = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, idx = jax.lax.top_k(z + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(z, idx, axis=-1)
    gates = chosen / chosen.sum(axis=-1, keepdims=True) * scale
    if return_scores:
        return idx.astype(jnp.int32), gates, z
    return idx.astype(jnp.int32), gates


def _gmm_tile(m: int) -> int:
    t = _GMM_MIN_TILE
    while t < _GMM_MAX_TILE and t * 16 < m:
        t *= 2
    return t


def _gmm_block_n(k: int, n: int, itemsize: int) -> int:
    """Widest lane-aligned divisor of ``n`` whose ``(k, tn)`` block
    stays under ``_GMM_BLOCK_BYTES``."""
    tn = n
    while tn % 256 == 0 and k * tn * itemsize > _GMM_BLOCK_BYTES:
        tn //= 2
    return tn


def _gmm_visits(group_sizes: jax.Array, m: int, tm: int):
    """The grouped matmul's schedule: one visit for every (expert,
    row-tile) pair whose rows intersect, experts in order, empty experts
    never visited.  Returns ``(group_ids, tile_ids, offsets, n_active)``
    with the id arrays padded by their last active entry, so that a
    padded grid step names the blocks already resident and moves
    nothing."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    tiles = jnp.where(
        group_sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    cum = jnp.cumsum(tiles).astype(jnp.int32)
    n_active = cum[-1]
    v_max = m // tm + g - 1
    v = jnp.minimum(jnp.arange(v_max, dtype=jnp.int32),
                    jnp.maximum(n_active - 1, 0))
    gid = jnp.minimum(
        jnp.searchsorted(cum, v, side="right").astype(jnp.int32), g - 1)
    tid = starts[gid] // tm + (v - (cum[gid] - tiles[gid]))
    tid = jnp.clip(tid, 0, m // tm - 1).astype(jnp.int32)
    return gid, tid, offsets, n_active.reshape(1)


def _clamp(a, b, limit):
    """The gated MLP's clamp (``swiglu_limit``): the gate's
    pre-activation from above, the up projection both ways."""
    return jnp.minimum(a, limit), jnp.clip(b, -limit, limit)


def _gmm_kernel(gid_ref, tid_ref, off_ref, nact_ref, x_ref, *refs,
                tm, swiglu, limit):
    o_ref = refs[-1]
    v = pl.program_id(1)

    @pl.when(v < nact_ref[0])
    def _():
        g = gid_ref[v]
        x = x_ref[...]
        if swiglu:
            a = jnp.dot(x, refs[0][0], preferred_element_type=jnp.float32)
            b = jnp.dot(x, refs[1][0], preferred_element_type=jnp.float32)
            if limit is not None:
                a, b = _clamp(a, b, limit)
            r = a * jax.nn.sigmoid(a) * b
        else:
            r = jnp.dot(x, refs[0][0], preferred_element_type=jnp.float32)
        rows = tid_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, r.shape, 0)
        mine = (rows >= off_ref[g]) & (rows < off_ref[g + 1])
        # A tile that several experts share is visited once by each, in
        # consecutive steps, and stays resident between them.
        o_ref[...] = jnp.where(mine, r.astype(o_ref.dtype), o_ref[...])


def _gmm_call(x, weights, visits, *, tm, swiglu, limit=None):
    """The ``pallas_call`` arguments of ``out[rows of expert e] =
    f(x[rows of expert e], weights[.][e])`` over the visits of
    :func:`_gmm_visits`; rows no expert owns are left as they lie (the
    caller masks them)."""
    m, k = x.shape
    n = weights[0].shape[2]
    tn = _gmm_block_n(k, n, weights[0].dtype.itemsize)
    w_spec = pl.BlockSpec(
        (1, k, tn), lambda j, v, gid, tid, off, nact: (gid[v], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, visits[0].shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k),
                         lambda j, v, gid, tid, off, nact: (tid[v], 0)),
            *([w_spec] * len(weights)),
        ],
        out_specs=pl.BlockSpec(
            (tm, tn), lambda j, v, gid, tid, off, nact: (tid[v], j)),
    )
    return dict(
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_GMM_VMEM_LIMIT,
        ),
        interpret=_interpret(),
    ), functools.partial(_gmm_kernel, tm=tm, swiglu=swiglu, limit=limit)


def grouped_swiglu(
    xs: jax.Array, group_sizes: jax.Array, w_gate: jax.Array,
    w_up: jax.Array, w_down: jax.Array, impl: str = "auto",
    limit: float = None,
) -> jax.Array:
    """``(silu(x Wg_e) * (x Wu_e)) Wd_e`` for rows sorted by expert;
    with a ``limit``, ``(silu(min(x Wg_e, limit)) * clip(x Wu_e, -limit,
    limit)) Wd_e``, the clamp in the gate/up kernel's epilogue.

    xs ``[M, d]``, the first ``group_sizes.sum()`` rows grouped by
    expert in expert order; w_gate / w_up ``[E, d, f]``; w_down
    ``[E, f, d]``.  Returns ``[M, d]``; rows past the groups are
    undefined under ``impl="pallas"`` (never computed) and zero under
    ``"xla"``.

    ``"pallas"``: two grouped matmuls (``rlt_moe_gate_up`` with the
    gate fused, ``rlt_moe_down``) that visit only (expert, row-tile)
    pairs with rows: an expert no row chose is neither read nor
    multiplied, and no row meets an expert it did not choose beyond its
    own tile.  ``"xla"``: a loop over the experts with masks (the CPU
    tests' and the rehearsal's path).  ``"auto"``: the kernels on a TPU
    unless ``RLT_DISABLE_KERNELS`` names ``moe``.
    """
    if impl == "auto":
        impl = "pallas" if (jax.default_backend() == "tpu"
                            and not kernel_family_disabled("moe")) else "xla"
    m = xs.shape[0]
    if impl == "xla":
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes
        rows = jnp.arange(m)

        def one(acc, e):
            wg, wu, wd, lo, hi = e
            a = jnp.dot(xs, wg, preferred_element_type=jnp.float32)
            b = jnp.dot(xs, wu, preferred_element_type=jnp.float32)
            if limit is not None:
                a, b = _clamp(a, b, limit)
            h = (a * jax.nn.sigmoid(a) * b).astype(xs.dtype)
            y = jnp.dot(h, wd, preferred_element_type=jnp.float32)
            mine = (rows >= lo) & (rows < hi)
            return jnp.where(mine[:, None], y.astype(xs.dtype), acc), None

        out, _ = jax.lax.scan(one, jnp.zeros_like(xs),
                              (w_gate, w_up, w_down, starts, ends))
        return out
    if impl != "pallas":
        raise ValueError(f"Unknown grouped matmul impl {impl!r} "
                         "(auto|xla|pallas)")
    tm = _gmm_tile(m)
    if m % tm:
        raise ValueError(f"grouped_swiglu: {m} rows are not whole tiles "
                         f"of {tm}")
    visits = _gmm_visits(group_sizes.astype(jnp.int32), m, tm)
    kw, kernel = _gmm_call(xs, (w_gate, w_up), visits, tm=tm, swiglu=True,
                           limit=limit)
    h = pl.pallas_call(kernel, name="rlt_moe_gate_up", **kw)(
        *visits, xs, w_gate, w_up)
    kw, kernel = _gmm_call(h, (w_down,), visits, tm=tm, swiglu=False)
    return pl.pallas_call(kernel, name="rlt_moe_down", **kw)(
        *visits, h, w_down)


def dropless_moe(
    x: jax.Array, idx: jax.Array, gates: jax.Array, w_gate: jax.Array,
    w_up: jax.Array, w_down: jax.Array, first_held: int,
    row_valid: jax.Array = None, impl: str = "auto", limit: float = None,
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a routed layer, nothing dropped.

    x ``[S, d]``; idx / gates ``[S, k]`` from the router over ALL
    experts; the weights are those of experts ``[first_held, first_held
    + E_held)``.  ``row_valid [S]`` (optional) takes padding rows and
    idle slots out of the routing.  Returns ``(y [S, d] in x's dtype:
    sum over the row's chosen experts held here of gate x expert(x),
    counts int32 [2]: assignments that fell on held experts, held
    experts hit)``.  ``limit``: the experts' clamp (:func:`grouped_swiglu`).
    """
    s, k = idx.shape
    e_held = w_gate.shape[0]
    local = idx - first_held
    here = (local >= 0) & (local < e_held)
    if row_valid is not None:
        here = here & row_valid[:, None]
    key = jnp.where(here, local, e_held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((e_held + 1,), jnp.int32).at[key].add(1)[:e_held]
    n_local = sizes.sum()
    counts = jnp.stack([n_local, (sizes > 0).sum().astype(jnp.int32)])
    m = s * k
    pad = -m % _gmm_tile(m)
    tok = jnp.pad(order // k, (0, pad))
    ys = grouped_swiglu(x[tok], sizes, w_gate, w_up, w_down, impl=impl,
                        limit=limit)
    # Back to (row, choice) order by the inverse permutation; a gather,
    # then one weighted sum over the k choices in float32.  Rows past
    # n_local were never computed: selected away, not multiplied.
    rank = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    back = jnp.where(here[..., None], ys[rank].reshape(s, k, -1), 0)
    y = jnp.einsum("skd,sk->sd", back.astype(jnp.float32), gates)
    return y.astype(x.dtype), counts
