"""KV-handoff wire format: prefill worker → decode replica frames.

The disaggregated serving plane's tensor frames reuse the queue-plane
conventions the MPMD transfer lane established (``mpmd/transfer.py``):
every frame is a small typed dict whose bulk payload rides EITHER
inline (``data`` bytes, chunk-sent by ``cluster/queue.py`` past 8MB —
the cross-host DCN form) OR as a tmpfs segment path (``shm`` — the
same-host zero-copy form, ``SegmentStore`` prefix ``rlt-kv``).
Consumers resolve either through ``transfer.resolve_payload`` (read
once, unlink once).

Frame families (envelopes schema-pinned in ``telemetry/schema.py``;
the tensor payload itself is an ``encode_tree`` blob, deliberately
outside the schema like MPMD activation bytes):

* ``serve_prefill_dispatch`` — router → prefill worker: the full
  client request plus the target decode replica's inbox address;
* ``serve_kv_handoff`` — prefill worker → decode replica: the request
  plus its exported per-layer KV blocks and final-position logits
  (``validate_serve_kv_handoff``);
* ``serve_replica_hello`` / ``serve_replica_beat`` — member → router:
  registration (inbox address + capabilities) and the periodic
  liveness/occupancy/completion feed the router's failover and
  placement decisions run on.

Everything here is jax-free given payload bytes, so the schema gate
(``tests/test_wire_schemas.py``) drives the REAL producers.
"""

from __future__ import annotations

import logging
import queue as _pyqueue
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

__all__ = [
    "KV_SEGMENT_PREFIX",
    "CachedSender",
    "MemberOutbox",
    "request_fields",
    "make_dispatch_item",
    "make_handoff_item",
    "make_adapter_load_item",
    "make_hello_item",
    "make_beat_item",
    "make_migration_item",
    "make_cancel_item",
    "encode_kv_payload",
    "decode_kv_payload",
]

log = logging.getLogger(__name__)


class CachedSender:
    """One persistent ``QueueHandle`` per destination address, evicted
    on send failure so the next attempt reconnects fresh — the send
    helper the router (dispatch/replies) and the prefill workers
    (handoffs) share, so dead-peer handling can only evolve in ONE
    place."""

    def __init__(self):
        self._handles: Dict[Tuple[str, int], Any] = {}

    def put(self, addr, item: Dict[str, Any]) -> None:
        from ray_lightning_tpu.cluster.queue import QueueHandle

        addr = (addr[0], int(addr[1]))
        handle = self._handles.get(addr)
        if handle is None:
            handle = QueueHandle(addr[0], addr[1])
            self._handles[addr] = handle
        try:
            handle.put(item)
        except BaseException:
            self._handles.pop(addr, None)
            raise

    def close(self) -> None:
        for handle in self._handles.values():
            handle.close()
        self._handles.clear()


class MemberOutbox:
    """Per-destination send thread with a bounded queue — the router's
    control plane must never block inside a TCP connect to a wedged
    member (the PR-12 documented limit: a blackholed host held the
    router lock for a full ~60s connect timeout, freezing every client
    of the fleet).  Sends enqueue in O(1); the outbox thread pays the
    network; a send failure (or a FULL queue — a member that stopped
    draining for ``maxsize`` frames is wedged) reports through
    ``on_error`` exactly once per incident, which the router routes
    into its existing death/failover path.

    ``put`` takes an optional ``on_sent(enqueue_ts)`` callback fired
    after the wire write completes — the tracer's ``placement`` span is
    recorded there, so it measures REAL dispatch latency (queue wait +
    connect + serialize + send), not the lock convoy the synchronous
    sender measured."""

    def __init__(self, addr: Tuple[str, int],
                 on_error: Optional[Callable[[BaseException], None]] = None,
                 maxsize: int = 256):
        self.addr = (addr[0], int(addr[1]))
        self._on_error = on_error
        self._q: _pyqueue.Queue = _pyqueue.Queue(maxsize=maxsize)
        self._sender = CachedSender()
        self._closed = threading.Event()
        self._dead = False
        self._sending = False
        # Idle-reap bookkeeping (the router closes outboxes that have
        # not sent for a while — clients come and go; their reply
        # lanes must not accumulate threads forever).
        self.last_used = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"rlt-outbox-{self.addr[0]}:{self.addr[1]}",
        )
        self._thread.start()

    def put(self, item: Dict[str, Any],
            on_sent: Optional[Callable[[float], None]] = None) -> None:
        """Enqueue one frame.  Raises ``ConnectionError`` when the
        outbox is already dead or its queue is full — the caller's
        existing (OSError, ConnectionError) handling then runs the same
        death path a synchronous send failure did."""
        if self._dead or self._closed.is_set():
            raise ConnectionError(f"outbox to {self.addr} is closed")
        self.last_used = time.monotonic()
        try:
            self._q.put_nowait((item, on_sent, time.monotonic()))
        except _pyqueue.Full:
            raise ConnectionError(
                f"outbox to {self.addr} is full ({self._q.maxsize} "
                f"frames undrained — member wedged?)"
            ) from None

    def _run(self) -> None:
        while not self._closed.is_set():
            try:
                item, on_sent, t_enq = self._q.get(timeout=0.2)
            except _pyqueue.Empty:
                continue
            self._sending = True
            try:
                try:
                    self._sender.put(self.addr, item)
                except Exception as e:  # noqa: BLE001 - any send
                    # failure marks the member; the router decides
                    # what it means
                    self._dead = True
                    if self._on_error is not None:
                        try:
                            self._on_error(e)
                        except Exception:  # noqa: BLE001 - observer bug
                            log.warning("outbox on_error raised",
                                        exc_info=True)
                    return
                if on_sent is not None:
                    try:
                        on_sent(t_enq)
                    except Exception:  # noqa: BLE001 - tracing is
                        # best-effort; a raising observer must not
                        # kill the lane
                        log.warning("outbox on_sent raised",
                                    exc_info=True)
            finally:
                self._sending = False

    @property
    def depth(self) -> int:
        return self._q.qsize()

    @property
    def pending(self) -> int:
        """Frames enqueued or mid-send (the flush condition)."""
        return self._q.qsize() + (1 if self._sending else 0)

    def close(self, drain_s: float = 2.0) -> None:
        """Stop the thread, best-effort draining queued frames first
        (a planned teardown should not drop the last replies).  Safe to
        call from the outbox thread itself (the error-callback path),
        and NEVER joins a dead box's thread — that thread may be
        blocked on the caller's own lock inside on_error, and it exits
        on its own the moment the callback returns (joining it from
        under the router lock would burn the full join timeout as a
        control-plane stall)."""
        deadline = time.monotonic() + drain_s
        while (not self._dead and self._q.qsize()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        self._closed.set()
        if (not self._dead
                and threading.current_thread() is not self._thread):
            self._thread.join(timeout=5)
        self._sender.close()


# Serve-plane handoff segments get their own family so teardown sweeps
# (engine close, router failover, actor kill) can collect dead prefill
# handoffs without touching a co-resident MPMD fit's rlt-seg frames.
KV_SEGMENT_PREFIX = "rlt-kv"


def request_fields(
    rid: str,
    prompt: Sequence[int],
    max_new_tokens: int,
    *,
    reply: Sequence,
    sample_seed: int,
    temperature: float = 0.0,
    eos_token_id: Optional[int] = None,
    top_k: Optional[int] = None,
    spec: Optional[int] = None,
    adapter: Optional[str] = None,
    deadline_s: Optional[float] = None,
    priority: int = 0,
    trace=None,
) -> Dict[str, Any]:
    """The canonical request dict that rides inside dispatch/handoff
    frames (a ``serve_request`` body with the router's fleet-wide
    ``sample_seed`` — and, on tracing routers, the request's
    ``TraceContext`` — attached).  ``priority`` is the brownout shed
    class: 0 (default) sheds first under overload, >= 1 survives."""
    item = {
        "type": "serve_request",
        "rid": str(rid),
        "prompt": [int(t) for t in prompt],
        "max_new_tokens": int(max_new_tokens),
        "temperature": float(temperature),
        "eos_token_id": eos_token_id,
        "top_k": None if top_k is None else int(top_k),
        "spec": None if spec is None else int(spec),
        "adapter": None if adapter is None else str(adapter),
        "deadline_s": deadline_s,
        "sample_seed": int(sample_seed),
        "priority": int(priority),
        "reply": list(reply),
    }
    if trace is not None:
        from ray_lightning_tpu.telemetry.propagate import inject

        inject(item, trace)
    return item


def make_dispatch_item(req: Dict[str, Any], kv_to: Tuple[str, int],
                       same_host: bool = False) -> Dict[str, Any]:
    """Router → prefill worker: run ``req``'s prompt and hand the KV
    off to the decode replica inbox at ``kv_to``.  ``same_host`` gates
    the tmpfs-segment payload form — the router computes it from the
    worker's and replica's advertised hosts; the default is the
    conservative inline-bytes form, which works anywhere (a tmpfs path
    shipped across hosts would fail every large handoff)."""
    return {
        "type": "serve_prefill_dispatch",
        "rid": req["rid"],
        "req": dict(req),
        "kv_to": [kv_to[0], int(kv_to[1])],
        "same_host": bool(same_host),
    }


def make_handoff_item(
    req: Dict[str, Any],
    bucket: int,
    *,
    data: Optional[bytes] = None,
    shm: Optional[str] = None,
    trace=None,
) -> Dict[str, Any]:
    """Prefill worker → decode replica: the prefilled request.  Exactly
    one of ``data``/``shm`` carries the ``encode_kv_payload`` blob.
    ``trace`` (the worker's prefill-span context) stamps the envelope
    with the wall-clock send time the replica books
    ``handoff_transfer`` from."""
    if (data is None) == (shm is None):
        raise ValueError("exactly one of data/shm payload required")
    item: Dict[str, Any] = {
        "type": "serve_kv_handoff",
        "rid": req["rid"],
        "bucket": int(bucket),
        "prompt_len": len(req["prompt"]),
        "req": dict(req),
    }
    if data is not None:
        item["data"] = data
    else:
        item["shm"] = shm
    if trace is not None:
        from ray_lightning_tpu.telemetry.propagate import inject

        inject(item, trace)
    return item


def make_adapter_load_item(
    name: str,
    rank: int,
    *,
    data: Optional[bytes] = None,
    shm: Optional[str] = None,
) -> Dict[str, Any]:
    """Router/operator → member (decode replica OR prefill worker):
    hot-load one tenant's LoRA adapter into the member's pool.
    Exactly one of ``data``/``shm`` carries the
    ``serve/lora.py::encode_adapter`` blob — the same dual transport
    as KV handoffs (inline bytes chunk-sent past 8MB cross-host, a
    tmpfs segment path same-host)."""
    if (data is None) == (shm is None):
        raise ValueError("exactly one of data/shm payload required")
    item: Dict[str, Any] = {
        "type": "serve_adapter_load",
        "name": str(name),
        "rank": int(rank),
    }
    if data is not None:
        item["data"] = data
    else:
        item["shm"] = shm
    return item


def make_hello_item(role: str, member_id: str, inbox: Tuple[str, int],
                    **caps: Any) -> Dict[str, Any]:
    """Member registration: the router learns the inbox address and the
    capabilities placement runs on (``num_slots``, ``max_queue``,
    ``spec_k``, ``max_prompt_len``)."""
    return {
        "type": "serve_replica_hello",
        "role": role,
        "id": str(member_id),
        "inbox": [inbox[0], int(inbox[1])],
        **caps,
    }


def make_beat_item(
    role: str,
    member_id: str,
    *,
    done: Sequence[Tuple[str, str]] = (),
    failed: Sequence[Tuple[str, str]] = (),
    snapshot: Optional[Dict[str, Any]] = None,
    recompiles: Optional[int] = None,
    adapters: Optional[Sequence[str]] = None,
    closing: bool = False,
    migrating: Optional[Sequence[str]] = None,
) -> Dict[str, Any]:
    """Periodic member liveness + completion feed.  ``done`` carries
    terminal ``(rid, status)`` pairs since the last beat (the router's
    in-flight pruning signal); ``failed`` carries ``(rid, error)``
    pairs a member could not serve (the router re-routes them);
    ``adapters`` advertises the member's loaded LoRA tenants
    (adapter-aware placement routes a tenant's requests to members
    already holding its factors); ``migrating`` claims a rid set whose
    live-KV export is in flight — the router suppresses beat-loss
    failover for the member until the claim resolves or expires."""
    item: Dict[str, Any] = {
        "type": "serve_replica_beat",
        "role": role,
        "id": str(member_id),
        "ts": time.time(),
        "done": [[str(r), str(s)] for r, s in done],
        "failed": [[str(r), str(e)] for r, e in failed],
    }
    if snapshot is not None:
        item["snapshot"] = snapshot
    if recompiles is not None:
        item["recompiles"] = int(recompiles)
    if adapters is not None:
        item["adapters"] = [str(a) for a in adapters]
    if closing:
        item["closing"] = True
    if migrating is not None:
        item["migrating"] = [str(r) for r in migrating]
    return item


def make_migration_item(
    req: Dict[str, Any],
    *,
    generated: Sequence[int],
    cur_token: int,
    seq_len: int,
    data: bytes,
    trace=None,
) -> Dict[str, Any]:
    """Draining replica → router → survivor replica: one resident
    sequence's live state.  ``req`` is the canonical ``request_fields``
    dict (reply address + fleet-wide ``sample_seed`` included — the
    position-keyed sampler makes the continued stream bitwise-identical
    on any survivor slot).  ``generated`` are the tokens already
    emitted, ``cur_token`` the last sampled token (the next decode
    tick's input), ``seq_len`` the KV positions written
    (``prompt_len + len(generated) - 1`` — the final sampled token's KV
    is never written until its own tick).  ``data`` is the
    ``encode_tree({"kv": ...})`` export of the sequence's blocks;
    migration frames ride the ordered beat lane, so the payload is
    always inline bytes (never a tmpfs segment that would dangle if the
    draining host dies)."""
    item: Dict[str, Any] = {
        "type": "serve_migration",
        "rid": str(req["rid"]),
        "req": dict(req),
        "generated": [int(t) for t in generated],
        "cur_token": int(cur_token),
        "seq_len": int(seq_len),
        "data": data,
    }
    if trace is not None:
        from ray_lightning_tpu.telemetry.propagate import inject

        inject(item, trace)
    return item


def make_cancel_item(rid: str) -> Dict[str, Any]:
    """Router → decode replica: drop ``rid`` wherever it is (queued or
    mid-decode), silently — the first-winner cancel of a hedged pair.
    The replica reports it terminal with status ``cancelled`` on its
    done feed (never to the client — the winner already replied)."""
    return {"type": "serve_cancel", "rid": str(rid)}


def encode_kv_payload(kv: Dict[str, Any], logits: Any) -> bytes:
    """Serialize a prefill's exported blocks + final-position logits
    (the handoff frame's bulk payload)."""
    from ray_lightning_tpu.mpmd.transfer import encode_tree

    return encode_tree({"kv": kv, "logits": logits})


def decode_kv_payload(item: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`encode_kv_payload` over a handoff frame
    (resolves data/shm; shm segments are read once and unlinked)."""
    from ray_lightning_tpu.mpmd.transfer import decode_tree, resolve_payload

    return decode_tree(resolve_payload(item))
