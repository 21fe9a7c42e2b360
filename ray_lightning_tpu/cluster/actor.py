"""Process actors: the worker-launch layer of the built-in control plane.

TPU-native analogue of the reference's ``RayExecutor`` actor
(``/root/reference/ray_lightning/ray_ddp.py:38-63``): a generic remote
process shell with ``set_env_var(s)``, ``get_node_ip``, device introspection
and an arbitrary-function runner (``execute``).  The reference creates one
Ray actor per GPU; here one actor ≙ one **TPU host** (a v4 host owns 4
chips; JAX is multi-controller SPMD).

Launch mechanics — deliberately Ray-like, NOT ``multiprocessing``-like:
the child is a fresh ``subprocess`` running a dedicated module entry
(``python -m ray_lightning_tpu.cluster.actor``), so the user's ``__main__``
is **never re-imported** (no ``if __name__ == "__main__"`` guard required
in user scripts, matching Ray-actor ergonomics) and the child does not
inherit the driver's libtpu/XLA runtime (TPU chips are single-owner per
process).  Code travels exclusively via cloudpickle, which serializes
``__main__``-defined functions by value.

RPC protocol: length-prefixed cloudpickle frames over a loopback TCP
socket; a random authkey passed through the child's stdin authenticates the
connection.  A dedicated receiver thread resolves
``concurrent.futures.Future`` objects, so the driver can poll futures while
pumping the distributed queue (reference ``util.py:55-68``).

Env-var plumbing matters: JAX reads ``XLA_FLAGS`` / ``JAX_PLATFORMS`` /
``TPU_VISIBLE_CHIPS`` / ``LIBTPU_INIT_ARGS`` at import time, so the actor's
env dict is applied in the child *before* any user function (and hence any
jax import) runs — the analogue of the reference broadcasting
``MASTER_ADDR``/seed env vars to actors before training
(``ray_ddp.py:215-228``).
"""

from __future__ import annotations

import itertools
import os
import socket
import subprocess
import sys
import threading
import time
import traceback
from concurrent.futures import Future
from typing import Any, Callable, Dict, Optional

from . import rpc

__all__ = ["ProcessActor", "RemoteError", "ActorDiedError"]


class RemoteError(RuntimeError):
    """An exception raised inside an actor, re-raised on the driver."""

    def __init__(self, actor_name: str, formatted_traceback: str):
        super().__init__(
            f"Remote call on actor {actor_name!r} failed:\n{formatted_traceback}"
        )
        self.actor_name = actor_name
        self.remote_traceback = formatted_traceback


class ActorDiedError(RuntimeError):
    """The actor process exited before answering (≙ Ray's RayActorError).

    The reference surfaces worker death as a raised Ray error from
    ``ray.get`` inside ``process_results`` (``util.py:55-68``); we do the
    same — failures propagate fast and crash the fit.

    Structured context rides as attributes so death reports can say
    *when* and *how* the rank died, not just that it did: ``exit_code``
    (agent/subprocess ``poll()``), ``rank``, ``last_heartbeat_age_s``
    (from the RunMonitor), ``actor_name``.  Raise sites fill what they
    know; the strategy layer adds the rest via :meth:`enrich`.
    """

    def __init__(self, message: str, *, actor_name=None, exit_code=None,
                 rank=None, last_heartbeat_age_s=None):
        super().__init__(message)
        self.actor_name = actor_name
        self.exit_code = exit_code
        self.rank = rank
        self.last_heartbeat_age_s = last_heartbeat_age_s

    def enrich(self, **fields) -> "ActorDiedError":
        """Fill unset context fields and fold them into the message
        (in place — the exception identity/traceback is preserved)."""
        notes = []
        for key in ("actor_name", "exit_code", "rank",
                    "last_heartbeat_age_s"):
            if key in fields and getattr(self, key) is None:
                setattr(self, key, fields[key])
        if self.rank is not None:
            notes.append(f"rank={self.rank}")
        if self.exit_code is not None:
            notes.append(f"exit_code={self.exit_code}")
        if self.last_heartbeat_age_s is not None:
            notes.append(
                f"last_heartbeat={self.last_heartbeat_age_s}s ago"
            )
        extra = fields.get("note")
        if notes or extra:
            detail = "; ".join(notes + ([extra] if extra else []))
            self.args = (f"{self.args[0]} [{detail}]",) + self.args[1:]
        return self


def _apply_env(env: Dict[str, str]) -> None:
    for k, v in env.items():
        os.environ[k] = str(v)


# ---------------------------------------------------------------------------
# Functions commonly shipped to actors (top-level so plain pickle also works)
# ---------------------------------------------------------------------------

def _remote_set_env_vars(env: Dict[str, str]) -> None:
    """≙ RayExecutor.set_env_vars (reference ``ray_ddp.py:44-49``)."""
    _apply_env(env)


def _remote_get_node_ip() -> str:
    """≙ RayExecutor.get_node_ip (reference ``ray_ddp.py:51-53``)."""
    return rpc.get_node_ip()


def _remote_get_host_stats() -> Dict[str, Any]:
    """Host load/memory of the actor's node (straggler context for the
    fleet telemetry report; jax-free — safe before/without PJRT init)."""
    from ray_lightning_tpu.telemetry.aggregate import host_stats

    return {"ip": rpc.get_node_ip(), **host_stats()}


def _remote_get_device_info() -> Dict[str, Any]:
    """TPU analogue of get_node_and_gpu_ids (reference ``ray_ddp.py:55-58``).

    Imports jax *inside the actor* (first touch of the accelerator) and
    reports the local device topology for the driver's rank/mesh mapping.
    """
    import jax

    devices = jax.local_devices()
    return {
        "ip": rpc.get_node_ip(),
        "process_index": jax.process_index(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
        "platform": devices[0].platform if devices else "none",
        "device_kinds": [d.device_kind for d in devices],
    }


# ---------------------------------------------------------------------------
# Child-side main loop
# ---------------------------------------------------------------------------

def _remote_dump_stacks() -> Dict[str, Any]:
    """Out-of-band forensics: py-stacks of every live thread
    (``sys._current_frames``) + best-effort device memory.

    Served on the child's **control lane**, so it answers even while a
    ``call`` (the fit) is wedged inside a collective — the whole point:
    the RunMonitor asks a *hung* worker what it is stuck on.
    """
    from ray_lightning_tpu.telemetry.flight_recorder import (
        format_all_stacks,
    )
    from ray_lightning_tpu.telemetry.heartbeat import device_memory_stats

    out: Dict[str, Any] = {
        "pid": os.getpid(),
        "ts": time.time(),
        "stacks": format_all_stacks(),
    }
    mem = device_memory_stats()
    if mem:
        out["device_memory"] = mem
    return out


def _remote_request_drain() -> Dict[str, Any]:
    """Control-lane drain request: the driver received the preemption
    notice (or the user asked for a graceful stop) and tells this
    worker to finish its in-flight step, checkpoint, and exit with
    ``PreemptedError``.  Served on the control lane so it lands even
    while the fit call is busy — that is the whole point."""
    from ray_lightning_tpu.fault import drain

    drain.request_drain("driver-request")
    return {"pid": os.getpid(), "draining": True}


_CONTROL_HANDLERS: Dict[str, Callable[..., Any]] = {
    "dump_stacks": _remote_dump_stacks,
    "ping": lambda: {"pid": os.getpid(), "ts": time.time()},
    "drain": _remote_request_drain,
}


def _encode_call_error(exc: BaseException) -> Any:
    """Error payload for the call lane: the formatted traceback, plus —
    for the fault-plane's typed exceptions — the exception BY VALUE, so
    the driver can catch ``PreemptedError`` as a type instead of
    grepping a RemoteError string.  Arbitrary user exceptions stay
    string-only (their classes may not exist driver-side)."""
    tb = traceback.format_exc()
    from ray_lightning_tpu.fault.drain import PreemptedError

    if isinstance(exc, PreemptedError):
        try:
            return {"tb": tb, "exc": rpc.dumps(exc)}
        except Exception:  # noqa: BLE001 - fall back to the string form
            pass
    return tb


def _decode_call_error(actor_name: str, payload: Any) -> BaseException:
    """Driver-side inverse of :func:`_encode_call_error`."""
    if isinstance(payload, dict):
        blob = payload.get("exc")
        if blob is not None:
            try:
                exc = rpc.loads(blob)
                exc.remote_traceback = payload.get("tb", "")
                return exc
            except Exception:  # noqa: BLE001 - unpicklable: degrade
                pass
        payload = payload.get("tb", "")
    return RemoteError(actor_name, payload)


_CONNECT_TIMEOUT_S = 60.0


def _child_main() -> None:
    """Entry point of the actor subprocess (``python -m ...cluster.actor``).

    Two lanes over one connection:

    * ``call`` — user functions, executed **sequentially** on a single
      worker thread (the pre-control-lane ordering contract: a queued
      call never overlaps the one before it);
    * ``ctl`` — small, jax-light control requests (stack dumps, pings)
      handled inline on the receive thread, so they answer even while
      a call is stuck in a collective.  This is what makes driver-side
      hang diagnosis possible at all.
    """
    host = sys.argv[1]
    port = int(sys.argv[2])
    # Preemption-safe drain: SIGTERM/SIGINT during a fit become a drain
    # request the loop honors at the next step boundary (fault/drain.py)
    # instead of killing the process mid-collective.  Must happen here —
    # signal handlers are only installable from the MAIN thread, and the
    # fit runs on the call-worker thread.
    from ray_lightning_tpu.fault import drain as _drain

    _drain.install_signal_handlers()
    authkey = bytes.fromhex(sys.stdin.readline().strip())
    sock = socket.create_connection(
        (host, port), timeout=_CONNECT_TIMEOUT_S
    )
    # The bound is for CONNECTING only.  Left on the socket it ends
    # the main loop's recv after a minute without driver traffic —
    # exiting the process under a fit that simply takes longer — and
    # caps sendall's total time, which a GB-scale result package passes.
    # A dead driver closes the socket; that, not silence, ends the loop.
    sock.settimeout(None)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    rpc.send_frame(sock, authkey)

    send_lock = threading.Lock()

    def reply(obj: Any) -> None:
        with send_lock:
            rpc.send_frame(sock, rpc.dumps(obj))

    import queue as _pyqueue

    calls: "_pyqueue.Queue" = _pyqueue.Queue()

    def call_worker() -> None:
        while True:
            msg = calls.get()
            if msg is None:
                return
            _, call_id, payload = msg
            try:
                fn, args, kwargs = payload
                result = fn(*args, **kwargs)
                out = ("ok", call_id, result)
            except BaseException as e:  # noqa: BLE001 - ship it all back
                out = ("err", call_id, _encode_call_error(e))
            try:
                reply(out)
            except (ConnectionError, OSError):
                return
            except BaseException:
                # Result not serializable — report that instead of dying.
                reply(
                    ("err", call_id,
                     "actor result failed to serialize:\n"
                     + traceback.format_exc())
                )

    worker = threading.Thread(
        target=call_worker, name="rlt-actor-calls", daemon=True
    )
    worker.start()

    while True:
        try:
            msg = rpc.loads(rpc.recv_frame(sock))
        except (ConnectionError, OSError):
            break
        kind = msg[0]
        if kind == "exit":
            reply(("bye", None, None))
            break
        if kind == "call":
            calls.put(msg)
        elif kind == "ctl":
            _, call_id, (op, kw) = msg
            handler = _CONTROL_HANDLERS.get(op)
            try:
                if handler is None:
                    raise ValueError(f"unknown control op {op!r}")
                out = ("ok", call_id, handler(**kw))
            except BaseException:  # noqa: BLE001
                out = ("err", call_id, traceback.format_exc())
            try:
                reply(out)
            except (ConnectionError, OSError):
                break
    sock.close()
    # The call worker is a daemon: a kill()-initiated exit must not wait
    # for a wedged fit call (≙ ray.kill's no-grace semantics).
    sys.exit(0)


def build_child_env(env: Dict[str, str]) -> Dict[str, str]:
    """Child environment = this process's env + overrides + import paths.

    Mirror the spawning process's import environment: cloudpickle
    serializes functions from importable modules *by reference*, so
    anything the driver can import (the user's project, this package from a
    source checkout, pytest-rootdir test modules) must be importable in the
    child too.  '' means cwd on sys.path; make that explicit.  Called on
    the host that actually spawns — the driver for local actors, the node
    agent for remote ones (whose sys.path, not the driver's, is what
    exists on that host).
    """
    child_env = dict(os.environ)
    child_env.update({k: str(v) for k, v in env.items()})
    spawner_path = [p if p else os.getcwd() for p in sys.path]
    pp = child_env.get("PYTHONPATH", "")
    extra = [p for p in pp.split(os.pathsep) if p and p not in spawner_path]
    child_env["PYTHONPATH"] = os.pathsep.join(spawner_path + extra)
    return child_env


def spawn_child(
    connect_host: str, port: int, authkey_hex: str, env: Dict[str, str]
) -> subprocess.Popen:
    """Start one actor child that dials ``connect_host:port`` and
    authenticates with ``authkey_hex`` (fed via stdin, never argv)."""
    proc = subprocess.Popen(
        [sys.executable, "-c",
         "from ray_lightning_tpu.cluster.actor import _child_main; "
         "_child_main()",
         connect_host, str(port)],
        stdin=subprocess.PIPE,
        env=build_child_env(env),
    )
    assert proc.stdin is not None
    proc.stdin.write(authkey_hex.encode() + b"\n")
    proc.stdin.flush()
    return proc


def _local_launcher(
    connect_host: str, port: int, authkey_hex: str,
    env: Dict[str, str], name: str,
):
    return spawn_child(connect_host, port, authkey_hex, env)


class ProcessActor:
    """A worker subprocess with a generic ``execute`` RPC (≙ ``RayExecutor``).

    ``launcher`` abstracts *where* the child process starts: the default
    spawns it on this host; :func:`..agent.agent_launcher` asks a remote
    node agent to spawn it on another host, with the child dialing back to
    this driver over TCP.  ``bind_host``/``advertise_host`` follow the
    queue's pattern: bind loopback for local children, ``0.0.0.0`` + the
    routable NIC address for remote ones.
    """

    _ids = itertools.count()

    def __init__(
        self,
        name: Optional[str] = None,
        env: Optional[Dict[str, str]] = None,
        startup_timeout_s: float = 120.0,
        launcher: Optional[Callable[..., Any]] = None,
        bind_host: str = "127.0.0.1",
        advertise_host: Optional[str] = None,
    ):
        self.name = name or f"rlt-actor-{next(self._ids)}"
        self._env = dict(env or {})
        authkey = os.urandom(16)

        server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        server.bind((bind_host, 0))
        server.listen(1)
        host, port = server.getsockname()
        connect_host = advertise_host or host

        try:
            self._proc = (launcher or _local_launcher)(
                connect_host, port, authkey.hex(), self._env, self.name
            )
        except BaseException:
            server.close()
            raise

        # Accept with timeout + child liveness polling — a child that dies
        # during startup must surface as ActorDiedError, never a hang.
        server.settimeout(1.0)
        conn: Optional[socket.socket] = None
        deadline = time.monotonic() + startup_timeout_s
        while conn is None:
            if self._proc.poll() is not None:
                server.close()
                raise ActorDiedError(
                    f"Actor {self.name!r} exited during startup "
                    f"(exit code {self._proc.returncode}).",
                    actor_name=self.name,
                    exit_code=self._proc.returncode,
                )
            if time.monotonic() > deadline:
                server.close()
                self._proc.terminate()
                raise ActorDiedError(
                    f"Actor {self.name!r} did not connect within "
                    f"{startup_timeout_s}s.",
                    actor_name=self.name,
                )
            try:
                conn, _ = server.accept()
            except socket.timeout:
                continue
        server.close()
        conn.settimeout(None)
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        if rpc.recv_frame(conn) != authkey:
            conn.close()
            self._proc.terminate()
            raise ActorDiedError(
                f"Actor {self.name!r} failed authentication.",
                actor_name=self.name,
            )
        self._conn = conn

        self._send_lock = threading.Lock()
        self._call_ids = itertools.count()
        self._pending: Dict[int, Future] = {}
        self._lock = threading.Lock()
        self._closed = False
        self._exit_sent = False
        self._conn_dead = False
        self._recv_thread = threading.Thread(
            target=self._receive_loop, name=f"{self.name}-recv", daemon=True
        )
        self._recv_thread.start()

    # -- receive path -------------------------------------------------------
    def _receive_loop(self) -> None:
        while True:
            try:
                msg = rpc.loads(rpc.recv_frame(self._conn))
            except (ConnectionError, OSError):
                self._fail_all_pending()
                return
            status, call_id, payload = msg
            if status == "bye":
                self._fail_all_pending()
                return
            with self._lock:
                fut = self._pending.pop(call_id, None)
            if fut is None:
                continue
            if status == "ok":
                fut.set_result(payload)
            else:
                fut.set_exception(_decode_call_error(self.name, payload))

    def _fail_all_pending(self) -> None:
        with self._lock:
            self._conn_dead = True
            pending, self._pending = self._pending, {}
        exit_code = self._proc.poll()
        for fut in pending.values():
            if not fut.done():
                fut.set_exception(
                    ActorDiedError(
                        f"Actor {self.name!r} died before answering "
                        f"(exit code {exit_code}).",
                        actor_name=self.name, exit_code=exit_code,
                    )
                )

    # -- submit path --------------------------------------------------------
    def _submit_msg(self, lane: str, payload: Any, what: str) -> Future:
        """Ship one (call_id-tagged) frame; return its pending Future.
        Shared by the call lane and the control lane."""
        if self._closed or self._conn_dead or self._proc.poll() is not None:
            raise ActorDiedError(
                f"Actor {self.name!r} is not alive.",
                actor_name=self.name, exit_code=self._proc.poll(),
            )
        fut: Future = Future()
        call_id = next(self._call_ids)
        with self._lock:
            self._pending[call_id] = fut
        try:
            with self._send_lock:
                rpc.send_frame(
                    self._conn, rpc.dumps((lane, call_id, payload))
                )
        except (OSError, ValueError) as e:
            with self._lock:
                self._pending.pop(call_id, None)
            raise ActorDiedError(
                f"Failed to submit {what} to actor {self.name!r}: {e}",
                actor_name=self.name, exit_code=self._proc.poll(),
            )
        # Close the race with _fail_all_pending(): if the connection died
        # between our aliveness check and the insert above, the swap may
        # have missed this future — TCP happily buffers bytes into a dying
        # socket, so the send alone proves nothing.
        with self._lock:
            if self._conn_dead and not fut.done():
                self._pending.pop(call_id, None)
                fut.set_exception(
                    ActorDiedError(
                        f"Actor {self.name!r} died during submit.",
                        actor_name=self.name, exit_code=self._proc.poll(),
                    )
                )
        return fut

    def submit(self, fn: Callable, *args: Any, **kwargs: Any) -> Future:
        """Asynchronously run ``fn(*args, **kwargs)`` in the actor.

        ≙ ``RayExecutor.execute.remote`` (reference ``ray_ddp.py:60-62``,
        submission at ``ray_ddp.py:349-353``).  Returns a standard
        ``concurrent.futures.Future``.
        """
        return self._submit_msg("call", (fn, args, kwargs), "call")

    def execute(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(fn, *args, **kwargs).result()

    # -- control lane -------------------------------------------------------
    def control(self, op: str, timeout: Optional[float] = 10.0,
                **kwargs: Any) -> Any:
        """Out-of-band control request (``dump_stacks``, ``ping``).

        Served by the child's receive thread, NOT the call worker — so
        it answers even while a submitted call is hung.  That is the
        mechanism behind the RunMonitor's stack dumps of stuck ranks.
        """
        return self._submit_msg("ctl", (op, kwargs), f"ctl:{op}").result(
            timeout
        )

    def dump_stacks(self, timeout: Optional[float] = 10.0) -> Dict[str, Any]:
        """Py-stacks of every thread in the actor + device memory
        (``_remote_dump_stacks``) — works mid-call by design."""
        return self.control("dump_stacks", timeout=timeout)

    def request_drain(self, wait: bool = False,
                      timeout: Optional[float] = 10.0) -> Any:
        """Ask the worker to gracefully drain its in-flight fit
        (control lane — lands even mid-call).  ``wait=False`` returns
        the pending Future so a driver-side preemption handler can fan
        the request out to every worker without serializing on acks."""
        fut = self._submit_msg("ctl", ("drain", {}), "ctl:drain")
        return fut.result(timeout) if wait else fut

    # -- RayExecutor-parity conveniences ------------------------------------
    def set_env_vars(self, env: Dict[str, str]) -> None:
        self._env.update(env)
        self.execute(_remote_set_env_vars, env)

    def get_node_ip(self) -> str:
        return self.execute(_remote_get_node_ip)

    def get_device_info(self) -> Dict[str, Any]:
        return self.execute(_remote_get_device_info)

    def get_host_stats(self) -> Dict[str, Any]:
        """Load/memory of the actor's host (straggler context)."""
        return self.execute(_remote_get_host_stats)

    # -- lifecycle ----------------------------------------------------------
    def is_alive(self) -> bool:
        return (
            not self._closed
            and not self._conn_dead
            and self._proc.poll() is None
        )

    def request_exit(self) -> None:
        """Ask the child to exit and do not wait: :meth:`kill` waits.
        Whoever kills a set of actors asks every one first.  Workers of
        one ``jax.distributed`` job leave through a barrier at exit, so
        one asked alone waits out its whole grace for the others and is
        then terminated (5 s a teardown, PR 32)."""
        if self._exit_sent:
            return
        self._exit_sent = True
        try:
            with self._send_lock:
                rpc.send_frame(self._conn, rpc.dumps(("exit",)))
        except (OSError, ValueError):
            pass

    def kill(self, timeout: float = 5.0) -> None:
        """Tear down the actor (≙ ``ray.kill(w, no_restart=True)``,
        reference ``ray_ddp.py:398-400``)."""
        if self._closed:
            return
        self._closed = True
        self.request_exit()
        deadline = time.monotonic() + timeout
        while self._proc.poll() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        if self._proc.poll() is None:
            self._proc.terminate()
            try:
                self._proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self._proc.kill()
        try:
            self._conn.close()
        except OSError:
            pass
        # Reclaim tmpfs the dead child may have leaked: a stage worker
        # killed mid-transfer leaves rlt-seg segments (and a serve
        # prefill worker killed mid-handoff leaves rlt-kv ones) whose
        # owner pid is gone — sweeping every family at every kill keeps
        # /dev/shm bounded even for crash-looping fleets (the next
        # SegmentStore() would sweep too, but only its own prefix, and
        # only if one is ever created again).
        try:
            from ray_lightning_tpu.cluster.shm import (
                ALL_PREFIXES, sweep_stale_segments,
            )

            sweep_stale_segments(ALL_PREFIXES)
        except Exception:  # noqa: BLE001 - janitorial, never raises out
            pass


if __name__ == "__main__":
    _child_main()
