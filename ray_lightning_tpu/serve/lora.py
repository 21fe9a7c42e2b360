"""Multi-tenant LoRA serving: one resident base model, many adapters.

``models/`` trains LoRA adapters and ``merge_lora`` folds one of them
into the base weights — fine for a single tenant, an HBM cliff for
many: each tenant's merged copy is a full resident model.  The serving
plane instead keeps ONE lora-free base resident and multiplexes up to
``max_adapters`` tenants over it:

* :class:`AdapterPool` — the host-side slot registry (free-list, hot
  add/remove, name → slot) over device-resident STACKED factor
  buffers: per hook site (attention qkv / proj), every adapter's A/B
  factors live in one ``(L, N+1, ...)`` array whose leading layer axis
  rides the engine's block scan exactly like the KV pool.  Slot 0 is
  reserved as the NULL adapter (zero factors — the base model), so
  requests without an adapter share the same program;
* **batched per-slot application** — each compiled dispatch takes a
  per-slot ``adapter_ids`` int32 OPERAND (never a shape) and applies
  ``y += (x @ A[ids]) @ B[ids]`` as a gathered einsum / Pallas BGMV
  kernel (``ops/lora.py``), so one decode/verify/prefill program
  serves ANY mix of tenants and hot add/remove never recompiles —
  the round-11 zero-recompile contract, test- and bench-asserted.

The pool mirrors :class:`~.kv_cache.BlockAllocator` discipline: the
registry is jax-free host state, device mutation happens through ONE
jitted scatter program built at pool init (slot index is an operand),
and misuse (unknown name, rank drift, capacity, double-add) raises
typed errors instead of corrupting a co-tenant's traffic.

Wire form: adapters ride the queue plane as ``serve_adapter_load``
frames (``serve/dist/handoff.py::make_adapter_load_item``) whose bulk
payload is :func:`encode_adapter` bytes — chunk-sent past 8MB exactly
like KV handoffs, so a router can hot-load a tenant onto any replica
or prefill worker mid-traffic.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

__all__ = [
    "ADAPTER_KEYS",
    "AdapterPool",
    "encode_adapter",
    "decode_adapter",
    "validate_adapter",
]

#: The four stacked factor tensors every adapter carries
#: (models/gpt.py::extract_lora emits exactly these + "scale").
ADAPTER_KEYS = ("qkv_a", "qkv_b", "proj_a", "proj_b")


def validate_adapter(adapter: Dict[str, Any], cfg, rank: int) -> None:
    """Shape/rank gate for one adapter against a pool's geometry.
    Raises ``ValueError`` — a mis-shaped adapter scattered into the
    stacked buffers would serve garbage to ONE tenant while every
    neighbour stays healthy, the quiet failure mode a multi-tenant
    pool must never allow."""
    if not isinstance(adapter, dict):
        raise ValueError(
            f"adapter must be a dict, got {type(adapter).__name__}"
        )
    missing = [k for k in ADAPTER_KEYS if k not in adapter]
    if missing:
        raise ValueError(f"adapter missing factor(s) {missing}")
    L, d = cfg.n_layer, cfg.d_model
    expect = {
        "qkv_a": (L, d, rank),
        "qkv_b": (L, rank, 3 * d),
        "proj_a": (L, d, rank),
        "proj_b": (L, rank, d),
    }
    for key, shape in expect.items():
        got = tuple(adapter[key].shape)
        if got != shape:
            raise ValueError(
                f"adapter factor {key!r} has shape {got}, pool expects "
                f"{shape} (rank {rank} over L={L}, d={d} — every "
                f"adapter in a pool shares the stacked-buffer rank)"
            )


def encode_adapter(adapter: Dict[str, Any]) -> bytes:
    """Serialize an adapter (factors + scale) for the queue plane —
    the ``serve_adapter_load`` frame's bulk payload, same codec as KV
    handoffs."""
    import numpy as np

    from ray_lightning_tpu.mpmd.transfer import encode_tree

    tree = {k: np.asarray(adapter[k]) for k in ADAPTER_KEYS}
    tree["scale"] = np.float32(adapter.get("scale", 1.0))
    return encode_tree(tree)


def decode_adapter(item: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`encode_adapter` over a ``serve_adapter_load``
    frame (resolves the data/shm payload form like a KV handoff)."""
    from ray_lightning_tpu.mpmd.transfer import decode_tree, resolve_payload

    tree = decode_tree(resolve_payload(item))
    tree["scale"] = float(tree["scale"])
    return tree


class AdapterPool:
    """Up to ``max_adapters`` LoRA adapters stacked in resident device
    buffers + the host-side slot registry (see module docstring).

    Thread-safe: loads arrive from the queue-drain path or driver
    threads while the serve loop dispatches — ``buffers`` is swapped
    atomically (immutable jax arrays under one reference), so an
    in-flight dispatch keeps the tree it already read, and a new slot
    can only be REFERENCED after :meth:`add` returned.
    """

    def __init__(self, model_cfg, max_adapters: int, rank: int,
                 dtype=None, impl: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.ops.lora import resolve_bgmv_impl

        if max_adapters < 1:
            raise ValueError(
                f"max_adapters must be >= 1, got {max_adapters}"
            )
        if rank < 1:
            raise ValueError(f"adapter rank must be >= 1, got {rank}")
        self.cfg = model_cfg
        self.max_adapters = max_adapters
        self.rank = rank
        dtype = jnp.float32 if dtype is None else dtype
        self.dtype = dtype
        L, d, N1 = model_cfg.n_layer, model_cfg.d_model, max_adapters + 1
        # Slot 0 = the NULL adapter: zero factors, delta exactly 0.0.
        self.buffers: Dict[str, jax.Array] = {
            "qkv_a": jnp.zeros((L, N1, d, rank), dtype),
            "qkv_b": jnp.zeros((L, N1, rank, 3 * d), dtype),
            "proj_a": jnp.zeros((L, N1, d, rank), dtype),
            "proj_b": jnp.zeros((L, N1, rank, d), dtype),
        }
        self.impl = impl or resolve_bgmv_impl()
        # ONE scatter program for any slot (slot index is an operand) —
        # built here so a hot add can never construct a fresh jit on
        # the request path (rlt-lint RLT001 guards add()).  NO buffer
        # donation: the atomic-swap thread-safety contract (an
        # in-flight dispatch keeps the tree it already read) requires
        # the OLD buffers to stay alive until every reader drops them —
        # donation would delete them under a concurrently-dispatching
        # serve tick.  Hot adds are rare; the copy is the price of the
        # contract.

        def _scatter(buffers, factors, slot):
            return {
                k: buffers[k].at[:, slot].set(
                    factors[k].astype(buffers[k].dtype)
                )
                for k in buffers
            }

        from ray_lightning_tpu.telemetry.program_ledger import ledgered_jit

        self._scatter_fn = ledgered_jit(_scatter, site="serve/lora_scatter")
        self._slots: Dict[str, int] = {}      # guarded by self._lock
        # LIFO free list, mirroring BlockAllocator: recently freed
        # slots re-issue first.
        self._free: List[int] = list(range(max_adapters, 0, -1))
        self._lock = threading.Lock()
        self.loads = 0
        self.unloads = 0

    # -- registry ------------------------------------------------------------
    @property
    def loaded(self) -> int:
        with self._lock:
            return len(self._slots)

    @property
    def slots_free(self) -> int:
        with self._lock:
            return len(self._free)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._slots)

    def slot_of(self, name: str) -> int:
        """Device slot for ``name``; raises ``KeyError`` when the
        adapter is not loaded (submit()'s typed-rejection path)."""
        with self._lock:
            return self._slots[name]

    def has(self, name: str) -> bool:
        with self._lock:
            return name in self._slots

    # -- device mutation -----------------------------------------------------
    def add(self, name: str, adapter: Dict[str, Any]) -> int:
        """Load (or replace) ``name``'s factors; returns its slot.

        Replacing reuses the existing slot — callers gate replacement
        of an IN-USE adapter (``ServeEngine.add_adapter`` refuses while
        any queued/active request references the name; the pool itself
        cannot see the scheduler).  The scale is folded into the B
        factors here, so dispatches need no per-slot scale operand.
        """
        import jax.numpy as jnp
        import numpy as np

        validate_adapter(adapter, self.cfg, self.rank)
        scale = float(adapter.get("scale", 1.0))
        with self._lock:
            slot = self._slots.get(name)
            if slot is None:
                if not self._free:
                    raise RuntimeError(
                        f"adapter pool full ({self.max_adapters} "
                        f"slots) — remove a tenant or raise "
                        f"ServeConfig.max_adapters"
                    )
                slot = self._free.pop()
                self._slots[name] = slot
            factors = {
                "qkv_a": jnp.asarray(np.asarray(adapter["qkv_a"])),
                "qkv_b": jnp.asarray(
                    np.asarray(adapter["qkv_b"]) * scale
                ),
                "proj_a": jnp.asarray(np.asarray(adapter["proj_a"])),
                "proj_b": jnp.asarray(
                    np.asarray(adapter["proj_b"]) * scale
                ),
            }
            self.buffers = self._scatter_fn(
                self.buffers, factors, np.int32(slot)
            )
            self.loads += 1
            return slot

    def remove(self, name: str) -> None:
        """Free ``name``'s slot back to the pool.  The stale factors
        stay in the buffer until the slot is re-issued — harmless by
        construction, because no request can resolve the name anymore
        (the same reasoning as freed KV blocks keeping stale content).
        """
        with self._lock:
            slot = self._slots.pop(name, None)
            if slot is None:
                raise KeyError(f"adapter {name!r} is not loaded")
            self._free.append(slot)
            self.unloads += 1

    # -- introspection -------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "loaded": len(self._slots),
                "slots_free": len(self._free),
                "max_adapters": self.max_adapters,
                "rank": self.rank,
                "loads": self.loads,
                "unloads": self.unloads,
                "impl": self.impl,
            }
