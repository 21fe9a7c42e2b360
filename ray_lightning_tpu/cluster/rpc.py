"""Wire protocol helpers for the built-in control plane.

The reference outsources its control plane to Ray core (C++ raylet/GCS,
``/root/reference/ray_lightning/ray_ddp.py:38-63`` uses ``@ray.remote``
actors).  This package ships its own minimal, dependency-free control plane;
this module is the shared serialization/framing layer:

* **cloudpickle payloads** — like Ray, arbitrary callables (including
  lambdas with captured metrics, the Tune-report trick at reference
  ``tune.py:130-134``) must cross process boundaries;
* **length-prefixed frames** over sockets for the distributed queue.

The data plane (gradients, activations) NEVER touches this layer — that is
XLA collectives over ICI/DCN.  Only control messages and (relatively small)
state streams flow here.
"""

from __future__ import annotations

import socket
import struct
from typing import Any

import cloudpickle

_LEN = struct.Struct("!Q")
_RECV_CHUNK = 1 << 24
# Public alias: callers that stream a frame in pieces (the queue's
# chunked sender) must emit the exact same header this module parses.
FRAME_HEADER = _LEN


def dumps(obj: Any) -> bytes:
    return cloudpickle.dumps(obj)


def loads(data: bytes) -> Any:
    return cloudpickle.loads(data)


def send_frame(sock: socket.socket, payload: bytes) -> None:
    # Two sendalls, not header+payload concatenation: payloads carry full
    # model state streams, and the concat would transiently double memory.
    sock.sendall(_LEN.pack(len(payload)))
    sock.sendall(payload)


def recv_exact(sock: socket.socket, n: int) -> bytes:
    # One buffer, filled in place: ``sock.recv(remaining)`` allocates
    # ``remaining`` bytes per call, which for a GB-scale state stream is
    # a GB-scale allocation for every few hundred KB received.
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        k = sock.recv_into(view[got:got + _RECV_CHUNK])
        if not k:
            raise ConnectionError("socket closed mid-frame")
        got += k
    return bytes(buf)


def recv_frame(sock: socket.socket, max_len: int = 0) -> bytes:
    """Receive one frame; ``max_len`` (if nonzero) rejects oversized
    claims before any allocation — used on pre-authentication reads."""
    (length,) = _LEN.unpack(recv_exact(sock, _LEN.size))
    if max_len and length > max_len:
        raise ConnectionError(
            f"frame of {length} bytes exceeds limit {max_len}"
        )
    return recv_exact(sock, length)


def send_obj(sock: socket.socket, obj: Any) -> None:
    send_frame(sock, dumps(obj))


def recv_obj(sock: socket.socket) -> Any:
    return loads(recv_frame(sock))


def find_free_port(host: str = "") -> int:
    """OS-assigned free port (reference ``ray_ddp.py:31-35``).

    Used by the driver to broker rendezvous addresses: the distributed
    queue server, and the ``jax.distributed.initialize`` coordinator
    (the analogue of MASTER_ADDR/MASTER_PORT at reference
    ``ray_ddp.py:215-228``).
    """
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind((host, 0))
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        return s.getsockname()[1]


def get_node_ip() -> str:
    """Best-effort routable IP of this node (≙ ``ray.util.get_node_ip_address``)."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            # No packets are sent; this just selects the egress interface.
            s.connect(("8.8.8.8", 80))
            return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
