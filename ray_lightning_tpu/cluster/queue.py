"""Distributed queue: worker → driver streaming channel.

TPU-native analogue of ``ray.util.queue.Queue`` as used by the reference
(``/root/reference/ray_lightning/ray_ddp.py:344-347`` creates it driver-side
and ships the handle to every worker; workers ``put`` thunks/metrics from
inside the fit loop, the driver drains them while polling futures,
``util.py:47-68``).

Implementation: the *server* lives in the driver process — an accept loop on
a TCP socket feeding a thread-safe in-memory queue.  The *handle*
(:class:`QueueHandle`) is a picklable ``(host, port)`` pair; any worker on
any host can connect and push cloudpickled items.  TCP (not a pipe) so the
same mechanism works across hosts of a TPU pod, exactly like Ray's
actor-backed queue works across a cluster.
"""

from __future__ import annotations

import queue as _pyqueue
import socket
import threading
import time
import uuid
from typing import Any, Optional, Tuple

from . import rpc

__all__ = ["DriverQueue", "QueueHandle"]

# Hard ceiling on the 1-byte ack read.  The ack read must never block
# forever while holding the handle lock: if the driver process is alive
# but its reader thread is wedged, a bare ``recv`` would hang every
# worker ``put`` with no failover.  A timeout surfaces as
# ``socket.timeout`` (an ``OSError``) and flows into the close-and-raise
# path, which the caller's reconnect retry handles.
_ACK_TIMEOUT_S = 60.0
# The frame send gets a size-scaled budget instead: checkpoint thunks
# and MPMD activations can be GBs/multi-MB, and a Python socket timeout
# caps sendall's TOTAL duration — a fixed 60s would hard-fail any
# payload needing longer on a slow inter-host link.  Budget assumes
# worst-case ~1 MiB/s sustained.
_MIN_SEND_THROUGHPUT = 1 << 20  # bytes/s
# Frames above this are sent in chunks with a PER-CHUNK timeout: one
# slow multi-MB activation then can't trip a whole-frame budget — as
# long as each ~8MB chunk makes progress inside its own budget, the
# send succeeds no matter how long the total takes (the MPMD transfer
# lane's DCN contract).
_SEND_CHUNK_BYTES = 8 << 20


def _send_timeout_s(payload_bytes: int) -> float:
    """Size-scaled socket budget.  Applied to every slow half of a
    ``put``: connect (SYN retry storms on a congested DCN hop scale
    with load too), each send chunk, and the post-send ack drain (the
    server acks only after the full frame is read AND enqueued — for a
    multi-MB payload that read itself takes payload/throughput)."""
    return max(_ACK_TIMEOUT_S, payload_bytes / _MIN_SEND_THROUGHPUT)


def _sendall_chunked(sock: socket.socket, payload: bytes,
                     chunk_bytes: int = _SEND_CHUNK_BYTES) -> None:
    """``sendall`` in ``chunk_bytes`` slices, re-arming the size-scaled
    timeout per slice — total duration is unbounded, per-slice progress
    is not."""
    view = memoryview(payload)
    for off in range(0, len(view), chunk_bytes):
        chunk = view[off:off + chunk_bytes]
        sock.settimeout(_send_timeout_s(len(chunk)))
        sock.sendall(chunk)


class QueueHandle:
    """Picklable client handle to a :class:`DriverQueue`.

    One persistent connection per process, lazily opened on first ``put``.
    """

    def __init__(self, host: str, port: int):
        self.host = host
        self.port = port
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()
        self._client_id = uuid.uuid4().hex
        self._seq = 0

    # -- pickling: drop the live socket; each unpickled copy is a fresh
    # producer with its own dedup identity --------------------------------
    def __getstate__(self):
        return {"host": self.host, "port": self.port}

    def __setstate__(self, state):
        self.host = state["host"]
        self.port = state["port"]
        self._sock = None
        self._lock = threading.Lock()
        self._client_id = uuid.uuid4().hex
        self._seq = 0

    def _connect(self, timeout: float = 60.0) -> socket.socket:
        if self._sock is None:
            s = socket.create_connection(
                (self.host, self.port), timeout=timeout
            )
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._sock = s
        return self._sock

    def put(self, item: Any) -> None:
        """Ship ``item`` to the driver (reference ``session.py:61-63``).

        Synchronous like ``ray.util.queue.Queue.put`` (an actor call): the
        server acks only after the item is in the driver's queue, so once
        ``put`` returns the item is visible to any subsequent drain.
        Fire-and-forget would race :func:`util.process_results`'s final
        drain — a worker future can resolve before its last in-flight
        frame lands, silently dropping late metrics/thunks.

        Exactly-once enqueue: every frame carries ``(client_id, seq)``;
        the reconnect retry resends the *same* seq, and the server drops
        replays it has already enqueued.  Without this, an ack lost after
        the server committed the item would make the retry a duplicate —
        fatal for thunk items (a ``tune.report``/checkpoint lambda would
        execute twice driver-side).
        """
        # Chaos injection point: a crash/hang on the queue send path
        # exercises what a wedged control plane does to the fit (beats
        # and metrics ride this same lane).
        from ray_lightning_tpu.fault import inject as _chaos

        _chaos.fire("queue_put")
        with self._lock:
            # Burn the seq up front: if both attempts fail after the server
            # already committed this frame (ack lost, then reconnect
            # refused), the number must never be reused for a different
            # item — the server would dedup-drop it while acking success.
            self._seq += 1
            payload = rpc.dumps((self._client_id, self._seq, item))
            try:
                self._put_once(payload)
            except (OSError, ConnectionError):
                # One reconnect attempt — the driver may have restarted the
                # accept loop between epochs.
                self.close()
                self._put_once(payload)

    def _put_once(self, payload: bytes) -> None:
        budget = _send_timeout_s(len(payload))
        # Connect under the size-scaled budget too: a congested DCN hop
        # that throttles the payload also drops SYNs, and a 60s cap
        # would give up on exactly the links the scaling exists for.
        sock = self._connect(timeout=budget)
        try:
            sock.settimeout(budget)
            # Length prefix, then the payload in per-timeout chunks.
            sock.sendall(rpc.FRAME_HEADER.pack(len(payload)))
            _sendall_chunked(sock, payload)
            # The ack drains only after the server has READ the whole
            # frame off its socket — scale the wait with the payload.
            sock.settimeout(budget)
            ack = sock.recv(1)
        except Exception:
            # The frame may be half-sent or its ack still in flight; the
            # connection's ack stream can no longer be trusted (a later
            # put would read THIS frame's late ack as its own).  Drop it.
            self.close()
            raise
        if ack != b"\x01":
            self.close()
            raise ConnectionError("queue server closed before ack")

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None


class DriverQueue:
    """Driver-side queue server (≙ ``ray.util.queue.Queue`` actor)."""

    def __init__(self, host: str = "127.0.0.1", advertise_host: Optional[str] = None):
        self._items: _pyqueue.Queue = _pyqueue.Queue()
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind((host, 0))
        self._server.listen(128)
        self._port = self._server.getsockname()[1]
        self._advertise_host = advertise_host or host
        self._closed = threading.Event()
        self._conns: set = set()
        self._conns_lock = threading.Lock()
        # Per-producer high-water marks for replay dedup (one entry per
        # worker process — bounded by world size).
        self._seen: dict = {}
        self._seen_lock = threading.Lock()
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="rlt-queue-accept", daemon=True
        )
        self._accept_thread.start()

    @property
    def handle(self) -> QueueHandle:
        return QueueHandle(self._advertise_host, self._port)

    # -- server side --------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._closed.is_set():
            try:
                conn, _ = self._server.accept()
            except OSError:
                return  # listener closed
            with self._conns_lock:
                self._conns.add(conn)
            t = threading.Thread(
                target=self._reader_loop, args=(conn,), daemon=True
            )
            t.start()

    def _reader_loop(self, conn: socket.socket) -> None:
        try:
            while not self._closed.is_set():
                frame = rpc.recv_frame(conn)
                if self._closed.is_set():
                    # Shutdown raced the recv: drop the frame unacked so
                    # the producer's put raises instead of getting a
                    # false-success ack into a queue nobody will drain.
                    break
                try:
                    cid, seq, item = rpc.loads(frame)
                except Exception:
                    # Garbage / old-protocol frame (the queue binds
                    # non-loopback in multi-host backends): drop the
                    # connection, never the reader thread.
                    break
                with self._seen_lock:
                    fresh = seq > self._seen.get(cid, 0)
                    if fresh:
                        self._seen[cid] = seq
                if fresh:
                    # With its receipt time: a consumer that drains in
                    # its own loop (the serve engine, blocked on the
                    # device for a whole tick) would otherwise date the
                    # item from the drain.
                    self._items.put((time.monotonic(), item))
                # Ack whether fresh or a replay (a replay means the ack —
                # not the item — was lost on the previous attempt).
                conn.sendall(b"\x01")
        except (ConnectionError, OSError):
            pass
        finally:
            conn.close()
            with self._conns_lock:
                self._conns.discard(conn)

    # -- driver consumption (reference util.py:47-52) -----------------------
    def empty(self) -> bool:
        return self._items.empty()

    def get_nowait(self) -> Any:
        return self._items.get_nowait()[1]

    def get(self, timeout: Optional[float] = None) -> Any:
        return self._items.get(timeout=timeout)[1]

    def get_nowait_stamped(self) -> Tuple[float, Any]:
        """``(time.monotonic() at receipt, item)``."""
        return self._items.get_nowait()

    def shutdown(self) -> None:
        self._closed.set()
        try:
            self._server.close()
        except OSError:
            pass
        # Close live reader connections too: a worker's next (acked) put
        # must fail fast instead of feeding a queue nobody will drain.
        # shutdown(SHUT_RDWR) first — close() alone does not wake a reader
        # thread blocked in recv on the same file description, which could
        # otherwise ack an item into the dead queue.
        with self._conns_lock:
            conns, self._conns = list(self._conns), set()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
