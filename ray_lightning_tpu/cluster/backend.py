"""Cluster backend abstraction + in-memory object store.

The reference leans on Ray core for four services (SURVEY §2.2): actor
scheduling, object transfer (``ray.put`` at ``ray_ddp.py:340``), the
distributed queue, and teardown.  This module provides those behind a small
interface so the framework runs:

* **LocalBackend** (default, zero deps): process actors on this machine —
  the analogue of ``ray.init()`` auto-bootstrapping a local cluster
  (reference ``ray_ddp.py:125-126``).  This is also the mode used on a TPU
  pod slice where an external launcher (GKE, xpk, mpirun) starts one driver
  per slice.
* **RayBackend**: if real Ray *is* installed, the same interface maps onto
  ``@ray.remote`` actors with resource reservations
  (``RayExecutor.options(num_cpus=..., resources=...)``, reference
  ``ray_ddp.py:183-189``) — keeping Ray as control plane while the data
  plane stays XLA/ICI.  Gated with the ``Unavailable`` pattern.

Object store: ``put()`` eagerly serializes with cloudpickle into an
:class:`ObjectRef` whose payload travels inside actor RPC messages — the
driver serializes the model **once** and every worker deserializes its own
copy, exactly the ``ray.put(model)`` / implicit-get dance of reference
``ray_ddp.py:339-353``.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional, Union

from . import rpc
from .actor import ProcessActor
from .queue import DriverQueue

__all__ = [
    "ObjectRef",
    "ClusterBackend",
    "LocalBackend",
    "RemoteBackend",
    "RayBackend",
    "get_backend",
    "ray_is_available",
]


def ray_is_available() -> bool:
    try:
        import ray  # noqa: F401

        return True
    except ImportError:
        return False


class ObjectRef:
    """An object reference (≙ ``ray.ObjectRef``), by value or by segment.

    Serialization happens exactly once at ``put`` time; each ``get`` call
    deserializes a fresh copy (so workers never alias driver state — the
    property the reference gets from Ray's object store).  Large payloads
    on a single-host backend travel *by segment*: the bytes live in one
    checksummed tmpfs segment (:mod:`..cluster.shm`, the plasma analogue)
    and only the path crosses the actor sockets — N local workers cost one
    write + N page-cache reads instead of N socket copies.
    """

    __slots__ = ("_payload", "_segment_path", "_nbytes")

    def __init__(self, payload: Optional[bytes] = None,
                 segment_path: Optional[str] = None, nbytes: int = 0):
        self._payload = payload
        self._segment_path = segment_path
        self._nbytes = len(payload) if payload is not None else nbytes

    @classmethod
    def from_object(cls, obj: Any) -> "ObjectRef":
        return cls(payload=rpc.dumps(obj))

    @classmethod
    def from_object_via_store(
        cls, obj: Any, store, min_segment_bytes: int
    ) -> "ObjectRef":
        """Spill to a segment when the payload is worth it; the caller
        guarantees every reader shares the store's host."""
        payload = rpc.dumps(obj)
        if len(payload) < min_segment_bytes:
            return cls(payload=payload)
        path = store.put(payload)
        return cls(segment_path=path, nbytes=len(payload))

    def get(self) -> Any:
        if self._segment_path is not None:
            from .shm import SegmentStore

            return rpc.loads(SegmentStore.get(self._segment_path))
        return rpc.loads(self._payload)

    def release(self) -> None:
        """Reclaim the backing segment NOW (idempotent).

        Segments otherwise live until backend shutdown — a strategy that
        runs many fits on one backend (the PBT path) would leak tmpfs RAM
        proportional to fits × model size.  After release, ``get()`` on
        this ref is invalid."""
        if self._segment_path is not None:
            try:
                os.unlink(self._segment_path)
            except OSError:
                pass
            self._segment_path = None
        self._payload = None

    @property
    def nbytes(self) -> int:
        return self._nbytes


class ClusterBackend:
    """Interface every control-plane backend implements."""

    def create_actor(
        self,
        name: str,
        env: Optional[Dict[str, str]] = None,
        num_cpus: float = 1,
        resources: Optional[Dict[str, float]] = None,
    ):
        raise NotImplementedError

    def put(self, obj: Any) -> ObjectRef:
        raise NotImplementedError

    def create_queue(self) -> DriverQueue:
        raise NotImplementedError

    def shutdown(self) -> None:
        pass


class LocalBackend(ClusterBackend):
    """Process actors on the local host (spawn).

    All readers share this host, so ``put`` spills payloads above
    ``min_segment_bytes`` (default 1 MiB, ``RLT_SEGMENT_MIN_BYTES``) into
    the shared-memory segment store instead of the RPC stream.
    """

    def __init__(self, min_segment_bytes: Optional[int] = None):
        from .shm import SegmentStore

        self._actors: List[ProcessActor] = []
        self._store = SegmentStore()
        self.min_segment_bytes = (
            min_segment_bytes
            if min_segment_bytes is not None
            else int(os.environ.get("RLT_SEGMENT_MIN_BYTES", 1 << 20))
        )

    def create_actor(
        self,
        name: str,
        env: Optional[Dict[str, str]] = None,
        num_cpus: float = 1,
        resources: Optional[Dict[str, float]] = None,
    ) -> ProcessActor:
        actor = ProcessActor(name=name, env=env)
        self._actors.append(actor)
        return actor

    def put(self, obj: Any) -> ObjectRef:
        return ObjectRef.from_object_via_store(
            obj, self._store, self.min_segment_bytes
        )

    def create_queue(self) -> DriverQueue:
        return DriverQueue()

    def shutdown(self) -> None:
        for a in self._actors:
            a.request_exit()
        for a in self._actors:
            try:
                a.kill()
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
        self._actors.clear()
        self._store.unlink_all()


class RemoteBackend(ClusterBackend):
    """Multi-host control plane over node agents — the "infinite laptop".

    ≙ Ray Client + multi-node scheduling in the reference (``README.md:
    82-95``): the driver (a workstation or a CPU-only coordinator VM) holds
    one :class:`.agent.AgentClient` per TPU host and places actors
    round-robin across them.  Actors dial the driver back directly, and
    the distributed queue binds all interfaces — so the only topology
    requirement is driver↔host TCP reachability, exactly Ray Client's.

    ``hosts``: list of ``"ip[:port]"`` agent addresses (or the
    ``RLT_HOSTS`` env var, comma-separated, via :func:`get_backend`).
    """

    def __init__(self, hosts: List[str], token: Optional[str] = None):
        from .agent import AgentClient

        if not hosts:
            raise ValueError("RemoteBackend needs at least one agent host")
        self._clients = [AgentClient(h, token=token) for h in hosts]
        self._rr = 0
        self._actors: List[ProcessActor] = []

    def create_actor(
        self,
        name: str,
        env: Optional[Dict[str, str]] = None,
        num_cpus: float = 1,
        resources: Optional[Dict[str, float]] = None,
    ) -> ProcessActor:
        from .agent import agent_launcher

        client = self._clients[self._rr % len(self._clients)]
        self._rr += 1
        actor = ProcessActor(
            name=name,
            env=env,
            launcher=agent_launcher(client),
            bind_host="0.0.0.0",
            advertise_host=rpc.get_node_ip(),
        )
        self._actors.append(actor)
        return actor

    def put(self, obj: Any) -> ObjectRef:
        return ObjectRef.from_object(obj)

    def create_queue(self) -> DriverQueue:
        return DriverQueue(host="0.0.0.0", advertise_host=rpc.get_node_ip())

    def shutdown(self) -> None:
        for a in self._actors:
            a.request_exit()
        for a in self._actors:
            try:
                a.kill()
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
        self._actors.clear()
        for c in self._clients:
            c.close()
        self._clients = []


class _RayActorAdapter:
    """Wraps a Ray actor handle behind the :class:`ProcessActor` surface."""

    def __init__(self, handle, name: str):
        self._handle = handle
        self.name = name

    def submit(self, fn: Callable, *args: Any, **kwargs: Any):
        ref = self._handle.execute.remote(fn, *args, **kwargs)
        return _RayFutureAdapter(ref)

    def execute(self, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        import ray

        return ray.get(self._handle.execute.remote(fn, *args, **kwargs))

    def set_env_vars(self, env: Dict[str, str]) -> None:
        from .actor import _remote_set_env_vars

        self.execute(_remote_set_env_vars, env)

    def get_node_ip(self) -> str:
        from .actor import _remote_get_node_ip

        return self.execute(_remote_get_node_ip)

    def get_device_info(self) -> Dict[str, Any]:
        from .actor import _remote_get_device_info

        return self.execute(_remote_get_device_info)

    def is_alive(self) -> bool:
        return True

    def request_exit(self) -> None:
        """``ray.kill`` gives no grace: nothing to ask ahead of it."""

    def kill(self, timeout: float = 5.0) -> None:
        import ray

        ray.kill(self._handle, no_restart=True)


class _RayFutureAdapter:
    """Duck-typed ``concurrent.futures.Future`` over a Ray object ref."""

    def __init__(self, ref):
        self._ref = ref

    def result(self, timeout: Optional[float] = None) -> Any:
        import ray

        return ray.get(self._ref, timeout=timeout)

    def done(self) -> bool:
        import ray

        ready, _ = ray.wait([self._ref], timeout=0)
        return bool(ready)

    def exception(self, timeout: Optional[float] = None):
        try:
            self.result(timeout=timeout)
            return None
        except Exception as e:  # noqa: BLE001
            return e


class RayBackend(ClusterBackend):
    """Real-Ray control plane, used only when Ray is installed.

    Actors are reserved with custom resources so the scheduler pins one
    actor per TPU host (e.g. ``resources={"TPU": 4}``) — the analogue of
    GPU reservations at reference ``ray_ddp.py:183-189``.
    """

    def __init__(self):
        import ray

        if not ray.is_initialized():
            ray.init()  # ≙ reference ray_ddp.py:125-126
        self._ray = ray
        self._actors: List[_RayActorAdapter] = []

    def create_actor(
        self,
        name: str,
        env: Optional[Dict[str, str]] = None,
        num_cpus: float = 1,
        resources: Optional[Dict[str, float]] = None,
    ) -> _RayActorAdapter:
        ray = self._ray

        @ray.remote
        class _Shell:
            def execute(self, fn, *args, **kwargs):
                return fn(*args, **kwargs)

        # runtime_env starts the worker process WITH the env in place —
        # import-time vars (JAX_PLATFORMS/XLA_FLAGS/TPU_VISIBLE_CHIPS) must
        # be set before the worker's first jax import, matching
        # ProcessActor's pre-exec semantics.
        handle = _Shell.options(
            num_cpus=num_cpus,
            resources=resources or None,
            name=name,
            runtime_env={"env_vars": {k: str(v) for k, v in (env or {}).items()}},
        ).remote()
        adapter = _RayActorAdapter(handle, name)
        self._actors.append(adapter)
        return adapter

    def put(self, obj: Any) -> ObjectRef:
        # Keep by-value semantics for interface uniformity; Ray's own object
        # store is still used for the RPC arguments themselves.
        return ObjectRef.from_object(obj)

    def create_queue(self) -> DriverQueue:
        return DriverQueue(host="0.0.0.0", advertise_host=rpc.get_node_ip())

    def shutdown(self) -> None:
        for a in self._actors:
            try:
                a.kill()
            except Exception:  # noqa: BLE001
                pass
        self._actors.clear()


def get_backend(
    name: Union[str, ClusterBackend, None] = None,
) -> ClusterBackend:
    """Select the control plane.

    ``name`` may be a ClusterBackend instance (used as-is — how a
    configured :class:`RemoteBackend` is passed through a strategy), or a
    string: priority explicit ``name`` > ``RLT_BACKEND`` env var >
    ``local``.  ``"remote"`` reads agent addresses from ``RLT_HOSTS``;
    ``"ray"`` requires Ray installed.
    """
    if isinstance(name, ClusterBackend):
        return name
    name = name or os.environ.get("RLT_BACKEND", "local")
    if name == "ray":
        if not ray_is_available():
            raise ImportError(
                "RLT_BACKEND=ray requested but Ray is not installed; "
                "falling back is disabled to avoid silent behavior changes."
            )
        return RayBackend()
    if name == "remote":
        hosts = [h for h in os.environ.get("RLT_HOSTS", "").split(",") if h]
        return RemoteBackend(hosts)
    if name == "local":
        return LocalBackend()
    raise ValueError(
        f"Unknown cluster backend {name!r} (expected local|remote|ray)"
    )
