"""Control-plane tests: process actors, distributed queue, result pump.

Parity targets: reference RayExecutor behavior (ray_ddp.py:38-63), queue
streaming (ray_ddp.py:344-347 + util.py:47-68), fail-fast worker-death
semantics (SURVEY §5).
"""

import os
import time

import pytest

from ray_lightning_tpu.cluster import (
    ActorDiedError,
    DriverQueue,
    LocalBackend,
    ObjectRef,
    ProcessActor,
    RemoteError,
    find_free_port,
)
from ray_lightning_tpu.util import process_results


# -- top-level fns shipped to actors ----------------------------------------

def _add(a, b):
    return a + b


def _read_env(name):
    return os.environ.get(name)


def _boom():
    raise ValueError("intentional failure inside actor")


def _put_through_queue(handle, n):
    for i in range(n):
        handle.put({"step": i})
    return "done"


def _put_thunk(handle, value):
    # A cloudpickled closure crossing the process boundary — the Tune-report
    # trick (reference tune.py:130-134).
    handle.put(lambda: value * 2)
    return "sent"


def _exit_hard():
    os._exit(17)


@pytest.fixture(scope="module")
def actor():
    """One actor for every case that leaves it alive (a spawn is the
    child's ``import jax``, seconds each); a case that kills its actor,
    or sets its environment at the spawn, starts its own."""
    a = ProcessActor(name="test-actor")
    yield a
    a.kill()


class TestProcessActor:
    def test_execute_roundtrip(self, actor):
        assert actor.execute(_add, 2, 3) == 5

    def test_execute_lambda(self, actor):
        # cloudpickle lets arbitrary closures cross, like Ray tasks.
        captured = 10
        assert actor.execute(lambda x: x + captured, 5) == 15

    def test_submit_is_async(self, actor):
        futs = [actor.submit(_add, i, i) for i in range(5)]
        assert [f.result() for f in futs] == [0, 2, 4, 6, 8]

    def test_env_vars(self):
        a = ProcessActor(name="env-actor", env={"RLT_TEST_SPAWN": "at-start"})
        try:
            assert a.execute(_read_env, "RLT_TEST_SPAWN") == "at-start"
            a.set_env_vars({"RLT_TEST_LATER": "later"})
            assert a.execute(_read_env, "RLT_TEST_LATER") == "later"
        finally:
            a.kill()

    def test_remote_error_propagates(self, actor):
        with pytest.raises(RemoteError, match="intentional failure"):
            actor.execute(_boom)
        # Actor survives an exception (like a Ray actor does).
        assert actor.execute(_add, 1, 1) == 2

    def test_actor_death_fails_pending_futures(self):
        a = ProcessActor(name="dying-actor")
        fut = a.submit(_exit_hard)
        with pytest.raises(ActorDiedError):
            fut.result(timeout=30)
        with pytest.raises(ActorDiedError):
            a.submit(_add, 1, 2)
        a.kill()

    def test_get_node_ip(self, actor):
        ip = actor.get_node_ip()
        assert isinstance(ip, str) and ip.count(".") == 3

    def test_kill_idempotent(self):
        a = ProcessActor(name="kill-actor")
        a.kill()
        a.kill()
        assert not a.is_alive()


class TestDriverQueue:
    def test_local_put_get(self):
        q = DriverQueue()
        q.handle.put({"a": 1})
        assert q.get(timeout=10) == {"a": 1}
        q.shutdown()

    def test_cross_process_streaming(self, actor):
        q = DriverQueue()
        try:
            result = actor.execute(_put_through_queue, q.handle, 5)
            assert result == "done"
            got = [q.get(timeout=10) for _ in range(5)]
            assert got == [{"step": i} for i in range(5)]
        finally:
            q.shutdown()

    def test_handle_repickles(self):
        import cloudpickle

        q = DriverQueue()
        h2 = cloudpickle.loads(cloudpickle.dumps(q.handle))
        h2.put("x")
        assert q.get(timeout=10) == "x"
        q.shutdown()

    def test_put_is_synchronous(self):
        """Once put() returns the item must be visible to a drain — no
        in-flight window (the process_results final-drain race)."""
        q = DriverQueue()
        h = q.handle
        for i in range(50):
            h.put(i)
            assert not q.empty(), f"put({i}) returned before item landed"
            assert q.get_nowait() == i
        q.shutdown()

    def test_replayed_frames_dedup(self):
        """A retry that resends an already-enqueued seq (lost ack) must
        not produce a duplicate item."""
        from ray_lightning_tpu.cluster import rpc as _rpc

        q = DriverQueue()
        h = q.handle
        h.put("first")
        assert q.get(timeout=10) == "first"
        # Forge the retry: resend seq=1 on a fresh connection, as the
        # reconnect path does when the ack (not the item) was lost.
        import socket as _s

        with _s.create_connection((h.host, h.port), timeout=10) as sock:
            replay = _rpc.dumps((h._client_id, 1, "first"))
            _rpc.send_frame(sock, replay)
            assert sock.recv(1) == b"\x01"  # replay is acked...
            fresh = _rpc.dumps((h._client_id, 2, "second"))
            _rpc.send_frame(sock, fresh)
            assert sock.recv(1) == b"\x01"
        assert q.get(timeout=10) == "second"  # ...but never re-enqueued
        assert q.empty()
        q.shutdown()

    def test_concurrent_producers_exactly_once(self):
        """8 threads × 50 acked puts: every item arrives exactly once
        (per-producer seq spaces + the server's seen-dict under lock)."""
        import threading

        q = DriverQueue()
        n_threads, n_items = 8, 50
        errors = []

        def producer(tid):
            h = q.handle  # fresh handle -> own client_id/seq space
            try:
                for i in range(n_items):
                    h.put((tid, i))
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(e)

        threads = [
            threading.Thread(target=producer, args=(t,))
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "producer hung"
        assert not errors, errors
        got = []
        while not q.empty():
            got.append(q.get_nowait())
        assert sorted(got) == [
            (t, i) for t in range(n_threads) for i in range(n_items)
        ]
        q.shutdown()

    def test_put_after_shutdown_fails_fast(self):
        """shutdown() must wake reader threads and refuse late puts —
        not ack items into a queue nobody will drain."""
        q = DriverQueue()
        h = q.handle
        h.put("warm")  # opens the persistent connection
        q.shutdown()
        time.sleep(0.1)
        with pytest.raises((ConnectionError, OSError)):
            h.put("late")


class TestProcessResults:
    def test_pump_callback_raising_keeps_fit_result(self, actor):
        """A raising on_item observer must neither deadlock the pump
        nor drop the futures' results (satellite: driver resilience)."""
        q = DriverQueue()
        seen = []

        def bad_observer(item):
            seen.append(item)
            raise RuntimeError("observer blew up")

        try:
            fut = actor.submit(_put_through_queue, q.handle, 3)
            with pytest.warns(UserWarning, match="stream-item callback"):
                out = process_results([fut], q, on_item=bad_observer)
            assert out == ["done"]
            assert seen == [{"step": i} for i in range(3)]
        finally:
            q.shutdown()

    def test_pump_tick_callback_raising_is_survived(self, actor):
        q = DriverQueue()

        def bad_tick():
            raise ValueError("tick broke")

        try:
            fut = actor.submit(_add, 2, 2)
            with pytest.warns(UserWarning, match="tick callback"):
                assert process_results([fut], q, on_tick=bad_tick) == [4]
        finally:
            q.shutdown()

    def test_multi_rank_producers_exactly_once_under_pump(self):
        """3 worker processes streaming concurrently while the driver
        pumps: every item arrives exactly once, in per-rank order, even
        with an observer that raises on some items."""
        q = DriverQueue()
        actors = [
            ProcessActor(name=f"mp-producer-{i}") for i in range(3)
        ]
        got = []

        def observer(item):
            got.append(item)
            if item["step"] % 5 == 0:
                raise RuntimeError("selective observer failure")

        try:
            futures = [
                a.submit(_put_through_queue, q.handle, 20) for a in actors
            ]
            out = process_results(futures, q, on_item=observer)
            assert out == ["done"] * 3
            assert len(got) == 60
            # per-producer FIFO survives the concurrency
            assert sorted(i["step"] for i in got) == sorted(
                list(range(20)) * 3
            )
        finally:
            for a in actors:
                a.kill()
            q.shutdown()

    def test_pump_drains_queue_and_returns_results(self, actor):
        q = DriverQueue()
        try:
            fut = actor.submit(_put_through_queue, q.handle, 3)
            seen = []
            out = process_results([fut], q, on_item=seen.append)
            assert out == ["done"]
            assert seen == [{"step": i} for i in range(3)]
        finally:
            q.shutdown()

    def test_thunks_execute_in_driver(self, actor):
        q = DriverQueue()
        try:
            fut = actor.submit(_put_thunk, q.handle, 21)
            process_results([fut], q)
            # The thunk ran driver-side during the pump; verify by running
            # another and checking handle_queue_item directly.
            actor.execute(_put_thunk, q.handle, 5)
            item = q.get(timeout=10)
            assert callable(item) and item() == 10
        finally:
            q.shutdown()

    def test_worker_failure_raises(self, actor):
        fut = actor.submit(_boom)
        with pytest.raises(RemoteError):
            process_results([fut], None)


class TestBackend:
    def test_object_ref_copies(self):
        ref = ObjectRef.from_object({"w": [1, 2, 3]})
        a, b = ref.get(), ref.get()
        assert a == b
        a["w"].append(4)
        assert ref.get() == {"w": [1, 2, 3]}  # no aliasing

    def test_local_backend_lifecycle(self):
        be = LocalBackend()
        a = be.create_actor("be-actor")
        assert a.execute(_add, 4, 4) == 8
        q = be.create_queue()
        q.handle.put(1)
        assert q.get(timeout=10) == 1
        q.shutdown()
        be.shutdown()
        assert not a.is_alive()


def test_find_free_port():
    p = find_free_port()
    assert 1024 <= p <= 65535


def test_actor_call_outlasts_the_connect_timeout():
    """The child's connect timeout must not stay on its socket: left
    there, a minute without driver traffic ends the child's receive
    loop — exiting the process under a fit that simply takes longer —
    and caps the total time ``sendall`` may take for a result package
    (found on the chip, PR 21: a cold-cache GPT-2-small fit died with
    "died before answering").  A fake driver, a child whose connect
    timeout is cut to 0.3 s, and a call that sleeps four times that."""
    import socket
    import subprocess
    import sys

    from ray_lightning_tpu.cluster import rpc

    server = socket.socket()
    server.bind(("127.0.0.1", 0))
    server.listen(1)
    server.settimeout(30)
    script = (
        "import ray_lightning_tpu.cluster.actor as a\n"
        "a._CONNECT_TIMEOUT_S = 0.3\n"
        "a._child_main()\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    child = subprocess.Popen(
        [sys.executable, "-c", script, "127.0.0.1",
         str(server.getsockname()[1])],
        stdin=subprocess.PIPE, env=env,
    )
    try:
        child.stdin.write(b"00ff\n")
        child.stdin.flush()
        conn, _ = server.accept()
        conn.settimeout(30)
        assert rpc.recv_frame(conn) == bytes.fromhex("00ff")

        def slow():
            import time as _t

            _t.sleep(1.2)
            return "answered"

        rpc.send_frame(conn, rpc.dumps(("call", 7, (slow, (), {}))))
        assert rpc.loads(rpc.recv_frame(conn)) == ("ok", 7, "answered")
        rpc.send_frame(conn, rpc.dumps(("exit",)))
        assert rpc.loads(rpc.recv_frame(conn))[0] == "bye"
        assert child.wait(10) == 0
    finally:
        child.kill()
        server.close()
