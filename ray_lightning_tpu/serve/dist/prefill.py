"""Prefill workers: dedicated prompt capacity for the disaggregated
serving plane.

Long prompts are the serving plane's head-of-line blocker: a monolith
engine interleaves bucketed prefill dispatches with the fixed-width
decode tick, so every admission stalls every in-flight token stream
for one trunk forward.  A prefill worker moves that work onto its OWN
device (its own mesh/params): it runs the SAME ``paged_prefill``
program the engine would, exports the resulting per-layer KV blocks to
host (``PagedKVCache.export_blocks``), and ships them — plus the
final-position logits — to the chosen decode replica's inbox as a
``serve_kv_handoff`` frame.  The replica scatters them into its own
free blocks and goes straight to decode: decode ticks never pay for
prompts again.

Transport mirrors the MPMD lane: same-host payloads ride
``SegmentStore`` tmpfs segments (prefix ``rlt-kv``; the consuming
replica unlinks on read), cross-host payloads ride inline bytes
through the chunk-sending ``QueueHandle``.  Unconsumed segments (a
replica died between handoff and read) are TTL-pruned here, swept by
pid at every teardown (engine close, router failover, actor kill).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ray_lightning_tpu.fault.inject import (
    FaultBlackhole, fire as _fault_fire, set_member,
)
from ray_lightning_tpu.serve.dist.handoff import (
    KV_SEGMENT_PREFIX, CachedSender, encode_kv_payload, make_beat_item,
    make_handoff_item, make_hello_item,
)

__all__ = ["PrefillRunner"]

log = logging.getLogger(__name__)

# Same-host handoffs above this ride tmpfs segments (the MPMD lane's
# threshold — kernel socket buffers both ways vs one tmpfs write).
_SHM_THRESHOLD_BYTES = 256 << 10
# Unconsumed segments older than this are presumed addressed to a dead
# replica and unlinked (consumed ones are already gone — the replica
# unlinks on read, so this unlink is an ENOENT no-op for them).
_SEGMENT_TTL_S = 120.0


class PrefillRunner:
    """One prefill worker: inbox + compiled prefill programs + the
    handoff send path.  Transport/process-agnostic — drive it on a
    thread in the driver process (tests, the example) or inside a
    :class:`~ray_lightning_tpu.cluster.actor.ProcessActor`
    (``replica.py::run_prefill_worker``)."""

    def __init__(self, worker_id: str, module, params, serve_cfg,
                 beat_handle, *, beat_s: float = 0.25,
                 shm_threshold: int = _SHM_THRESHOLD_BYTES,
                 segment_ttl_s: float = _SEGMENT_TTL_S,
                 trace_dir: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.cluster.queue import DriverQueue
        from ray_lightning_tpu.models.generate import _reject_unmerged_lora
        from ray_lightning_tpu.serve.kv_cache import (
            GPTServeFamily, PagedKVCache, PrefixIndex, paged_prefill,
            paged_verify_step,
        )
        from ray_lightning_tpu.serve.scheduler import derive_geometry

        self.worker_id = worker_id
        self.module = module
        self.cfg = module.config
        self.serve_cfg = serve_cfg
        _reject_unmerged_lora(params)
        self._c = module._compute_dtype()
        # Held as the engine holds it (the same method of the same
        # family): a worker and its replicas read equal dtypes.
        self.params = GPTServeFamily(self.cfg).prepare_params(
            jax.tree.map(jnp.asarray, params), self._c)
        self.max_model_len, self.buckets = derive_geometry(
            serve_cfg, self.cfg
        )
        # The worker's pool only ever holds ONE in-flight prompt (the
        # dispatch loop is sequential): the largest bucket's blocks
        # plus the reserved trash block.  With the prefix cache on, the
        # pool also hosts RESIDENT chains between dispatches, so it is
        # sized like an engine pool (cfg.num_blocks, or a few buckets'
        # worth) — eviction, not sizing, handles the pressure.
        blocks_per_bucket = self.buckets[-1] // serve_cfg.block_size
        pool_blocks = blocks_per_bucket + 1
        if getattr(serve_cfg, "prefix_cache", False):
            pool_blocks = max(
                pool_blocks,
                getattr(serve_cfg, "num_blocks", None)
                or 4 * blocks_per_bucket + 1,
            )
        self.cache = PagedKVCache(
            self.cfg, pool_blocks, serve_cfg.block_size, dtype=self._c,
        )
        self._pool = self.cache.init_pool()
        cfg, c = self.cfg, self._c
        # Multi-tenant LoRA: the worker mirrors the decode replicas'
        # adapter pool (serve/lora.py) — a tenant's prompt must be
        # prefilled THROUGH its adapter or the handed-off KV would be
        # the base model's.  The router hot-loads adapters here over
        # the same serve_adapter_load frames replicas get.
        self.adapters = None
        if getattr(serve_cfg, "max_adapters", 0) > 0:
            from ray_lightning_tpu.serve.lora import AdapterPool

            self.adapters = AdapterPool(
                self.cfg, serve_cfg.max_adapters,
                serve_cfg.adapter_rank, dtype=self._c,
            )
        lora_impl = self.adapters.impl if self.adapters is not None \
            else "xla"

        def _prefill(params, pool, tokens, prompt_len, block_ids,
                     ad, ad_id):
            return paged_prefill(cfg, params, pool, tokens, prompt_len,
                                 block_ids, compute_dtype=c,
                                 adapters=ad, adapter_id=ad_id,
                                 lora_impl=lora_impl)

        from ray_lightning_tpu.telemetry.program_ledger import ledgered_jit

        # One executable per bucket length, like the engine's set.
        self._prefill_fn = ledgered_jit(_prefill, site="serve/dist_prefill")

        def _suffix(params, pool, table_row, start, tokens, limit,
                    sample_idx, ad, ad_ids):
            # Suffix-only prefill over claimed prefix blocks: the
            # engine's chunk program minus the sampling tail (a prefill
            # WORKER ships final-position logits, it never samples —
            # the consuming replica's _first program does, bitwise the
            # local path).  Window writes land at start + [0, T); the
            # claimed frontier sits strictly below start, so resident
            # chain blocks are read-only here.
            logits, pool = paged_verify_step(
                cfg, params, pool, table_row, start, tokens, limit,
                compute_dtype=c, adapters=ad, adapter_ids=ad_ids,
                lora_impl=lora_impl,
            )
            pick = jax.lax.dynamic_index_in_dim(
                logits[0], sample_idx, axis=0, keepdims=False
            )
            return pick, pool

        # One executable per suffix bucket width (the same bounded set
        # the bucketed prefill compiles over).
        self._suffix_fn = ledgered_jit(_suffix, site="serve/dist_suffix")
        # Prefix-aware KV reuse on the worker: a dispatch whose prompt
        # shares a resident whole-block prefix claims those blocks by
        # refcount and computes ONLY the suffix — the export still
        # covers the full bucket, so the handoff wire format (and the
        # consuming replica) are unchanged.
        self.prefix: Optional[PrefixIndex] = None
        if getattr(serve_cfg, "prefix_cache", False):
            self.prefix = PrefixIndex(
                self.cache.allocator, serve_cfg.block_size
            )
        self._inbox = DriverQueue()
        self._beat_handle = beat_handle
        self.beat_s = beat_s
        self._shm_threshold = shm_threshold
        self._segment_ttl_s = segment_ttl_s
        self._store = None           # SegmentStore, lazily created
        self._out = CachedSender()
        # Work thread appends, beat thread prunes/drains: everything
        # below is shared between them (the PR-12 review races).
        self._feed_lock = threading.Lock()
        # guarded by self._feed_lock
        self._live_segments: List[Tuple[str, float]] = []
        self._done: List[Tuple[str, str]] = []    # guarded by self._feed_lock
        self._failed: List[Tuple[str, str]] = []  # guarded by self._feed_lock
        self._last_beat = 0.0
        self.prefills = 0
        self.suffix_prefills = 0  # dispatches served over a claimed prefix
        # Distributed tracing: worker-side spans continue the router-
        # stamped request context (SpanTracer.start_remote), exported
        # at close for trace_collect.py to stitch.
        from ray_lightning_tpu.telemetry.spans import SpanTracer

        self._trace_dir = trace_dir
        self.tracer = SpanTracer(
            enabled=trace_dir is not None, maxlen=16384, rank=0,
            clock=time.time,
        )
        # Hard-kill simulation (InprocPrefill.kill(hard=True)): a dead
        # process sends no final beat — suppress the closing flag so
        # the router takes the death path, not the planned-drain one.
        self.suppress_final = False

    @property
    def handle(self):
        return self._inbox.handle

    def hello(self) -> None:
        """Register with the router: inbox address + the geometry caps
        placement and validation run on."""
        self._beat_handle.put(make_hello_item(
            "prefill", self.worker_id,
            (self._inbox.handle.host, self._inbox.handle.port),
            max_prompt_len=self.buckets[-1],
            max_model_len=self.max_model_len,
            block_size=self.serve_cfg.block_size,
            max_adapters=getattr(self.serve_cfg, "max_adapters", 0),
        ))

    # -- the loop ------------------------------------------------------------
    def step(self, timeout: float = 0.1) -> bool:
        """Process at most one dispatch; returns True when one was."""
        import queue as _pyqueue

        try:
            item = self._inbox.get(timeout=timeout)
        except _pyqueue.Empty:
            return False
        try:
            self._process(item)
        except Exception as e:  # noqa: BLE001 - a bad dispatch must
            # surface as a failed rid the router re-routes, never kill
            # the worker loop
            rid = item.get("rid") if isinstance(item, dict) else None
            log.warning("prefill %s: dispatch failed: %s",
                        self.worker_id, e, exc_info=True)
            if rid is not None:
                with self._feed_lock:
                    self._failed.append((str(rid), repr(e)))
        return True

    def run(self, stop=None) -> None:
        """Serve dispatches until ``stop()`` goes true (a
        ``threading.Event.is_set`` inproc, the fault plane's
        ``drain_requested`` inside an actor).

        Beats ride their OWN thread, so they keep flowing while the
        work loop sits inside a multi-second prefill compile — the same
        asymmetry the training monitor's heartbeat publisher relies on.
        A beat-starved worker would be declared lost and its dispatches
        redundantly re-routed on its very first compile."""
        set_member("prefill", self.worker_id)
        self.hello()
        done = threading.Event()

        def beat_loop():
            # Member identity is thread-local: the beat thread declares
            # its own so worker:-pinned beat faults fire here too.
            set_member("prefill", self.worker_id)
            while not done.is_set():
                self._maybe_beat()
                done.wait(min(self.beat_s, 0.1))

        beater = threading.Thread(
            target=beat_loop, name=f"rlt-prefill-beat-{self.worker_id}",
            daemon=True,
        )
        beater.start()
        try:
            while not (stop() if stop is not None else False):
                self.step(timeout=min(self.beat_s, 0.1))
        finally:
            done.set()
            beater.join(timeout=10)
            if not self.suppress_final:
                try:
                    # Final done/failed feed, flagged as a PLANNED
                    # drain — without `closing` the router would read
                    # this scale-down as a death: failure counters, a
                    # burnt respawn-governor slot, and a replacement
                    # worker the operator just tried to remove.
                    self._maybe_beat(force=True, closing=True)
                except Exception:  # noqa: BLE001 - router may be gone
                    pass
            self.close()

    def _process(self, item: Dict[str, Any]) -> None:
        import jax.numpy as jnp
        import numpy as np

        if isinstance(item, dict) \
                and item.get("type") == "serve_adapter_load":
            # Tenant hot-load: the router ensures the load frame lands
            # BEFORE any of the tenant's dispatches (one ordered inbox
            # lane per member), so resolution below never races it.
            from ray_lightning_tpu.serve.lora import decode_adapter

            if self.adapters is None:
                raise ValueError(
                    "serve_adapter_load on a prefill worker without an "
                    "adapter pool (serve_cfg.max_adapters == 0)"
                )
            _fault_fire("adapter_load", rid=str(item.get("name", "")))
            name = str(item["name"])
            if self.prefix is not None:
                # A hot-(re)load may replace the adapter's weights:
                # chains prefilled through the old weights are stale.
                # _process runs only on the work thread, so the drop
                # needs no deferral (unlike the engine's step-drained
                # queue).
                self.prefix.drop(name)
            self.adapters.add(name, decode_adapter(item))
            return
        if not (isinstance(item, dict)
                and item.get("type") == "serve_prefill_dispatch"):
            raise ValueError(
                f"unexpected item on prefill inbox: {type(item).__name__}"
            )
        req = item["req"]
        rid = str(req["rid"])
        adapter = req.get("adapter")
        ad, ad_id = None, None
        if self.adapters is not None:
            ad = self.adapters.buffers
            # Unknown tenant raises → the failed feed → router
            # re-routes (and re-ensures the load) — never a silent
            # base-model prefill for a tenant's prompt.
            ad_id = np.int32(0 if adapter is None
                             else self.adapters.slot_of(adapter))
        elif adapter is not None:
            raise ValueError(
                f"dispatch names adapter {adapter!r} but this worker "
                f"has no adapter pool"
            )
        prompt = [int(t) for t in req["prompt"]]
        bucket = next(b for b in self.buckets if b >= len(prompt))
        n_blocks = bucket // self.serve_cfg.block_size
        claimed: List[int] = []
        if self.prefix is not None:
            # Same cap as the engine's claim hook: the FINAL prompt
            # token's block is always computed here — its forward
            # produces the logits the handoff ships.
            cap = (len(prompt) - 1) // self.serve_cfg.block_size
            claimed = self.prefix.claim(adapter, prompt, cap)
        start = len(claimed) * self.serve_cfg.block_size
        ids = self.cache.allocator.alloc(n_blocks - len(claimed))
        if ids is None and self.prefix is not None:
            # Cache pressure: shed cold chains first, then (if this
            # very claim pins too much) fall back to a full recompute
            # with the cache flushed — never fail the dispatch.
            self.prefix.evict(n_blocks - len(claimed))
            ids = self.cache.allocator.alloc(n_blocks - len(claimed))
            if ids is None:
                if claimed:
                    self.cache.allocator.free(claimed)
                    claimed, start = [], 0
                self.prefix.evict(n_blocks)
                ids = self.cache.allocator.alloc(n_blocks)
            if ids is None:
                self.prefix.drop_all()
                ids = self.cache.allocator.alloc(n_blocks)
        assert ids is not None, "worker pool sized for the largest bucket"
        ids = list(claimed) + list(ids)
        req_ctx = None
        if self.tracer.enabled:
            from ray_lightning_tpu.telemetry.propagate import extract

            req_ctx = extract(req)  # the router-stamped trace root
        with self.tracer.start_remote(
                req_ctx, "prefill_compute", rid=rid,
                worker=self.worker_id, bucket=bucket) as pf_span:
            ok = False
            try:
                if start == 0:
                    padded = np.zeros((bucket,), np.int32)
                    padded[: len(prompt)] = prompt
                    logits, self._pool = self._prefill_fn(
                        self.params, self._pool, jnp.asarray(padded),
                        np.int32(len(prompt)),
                        jnp.asarray(np.asarray(ids, np.int32)),
                        ad, ad_id,
                    )
                else:
                    # Shared prefix resident: compute ONLY the suffix.
                    suffix = len(prompt) - start
                    width = next(b for b in self.buckets if b >= suffix)
                    window = np.zeros((1, width), np.int32)
                    window[0, :suffix] = prompt[start:]
                    row = np.zeros(
                        (1, self.buckets[-1]
                         // self.serve_cfg.block_size), np.int32,
                    )  # TRASH-padded past the prompt's blocks
                    row[0, : len(ids)] = ids
                    ad_ids = None if ad is None else jnp.asarray(
                        [int(ad_id)], jnp.int32
                    )
                    logits, self._pool = self._suffix_fn(
                        self.params, self._pool, jnp.asarray(row),
                        jnp.asarray(np.full((1,), start, np.int32)),
                        jnp.asarray(window),
                        jnp.asarray(np.full((1,), len(prompt),
                                            np.int32)),
                        np.int32(suffix - 1), ad, ad_ids,
                    )
                    self.suffix_prefills += 1
                # export_blocks device_gets the blocks, so the span
                # closes on a SYNCED device — real prefill compute.
                kv = self.cache.export_blocks(self._pool, ids)
                ok = True
            finally:
                if ok and self.prefix is not None:
                    # Publish the whole-block prompt prefix; the index
                    # retains the chain, so the free below only drops
                    # THIS dispatch's handles and resident blocks
                    # survive for the next sharing prompt to claim.
                    n_full = len(prompt) // self.serve_cfg.block_size
                    if n_full:
                        self.prefix.insert(
                            adapter, prompt, ids[:n_full]
                        )
                self.cache.allocator.free(ids)
        with self.tracer.start_remote(
                pf_span.ctx, "handoff_send", rid=rid) as send_span:
            payload = encode_kv_payload(kv, np.asarray(logits))
            # The envelope carries the WORKER's span + send timestamp:
            # the consuming replica books handoff_transfer from it and
            # its admission spans parent under this worker's spans.
            handoff_trace = send_span.ctx or pf_span.ctx
            shm_path = None
            if item.get("same_host", False) \
                    and len(payload) >= self._shm_threshold:
                shm_path = self._segment_store().put(payload)
                with self._feed_lock:  # beat thread prunes concurrently
                    self._live_segments.append((shm_path,
                                                time.monotonic()))
                out = make_handoff_item(req, bucket, shm=shm_path,
                                        trace=handoff_trace)
            else:
                out = make_handoff_item(req, bucket, data=payload,
                                        trace=handoff_trace)
        try:
            # Serve fault grammar: shm_vanish unlinks the segment here
            # (the consumer's read then fails retryably), torn corrupts
            # it, blackhole drops the frame below.
            _fault_fire("handoff_send", rid=rid, path=shm_path)
        except FaultBlackhole:
            # Injected partition: the frame is "sent" but never
            # arrives.  An shm segment ages out via the TTL janitor,
            # exactly like a real replica death between send and read;
            # recovery is client/router-driven (deadline + retry).
            return
        try:
            self._put(tuple(item["kv_to"]), out)
        except (OSError, ConnectionError) as e:
            # The replica's inbox is unreachable (dying or dead): give
            # the segment back ourselves (no consumer will unlink it)
            # and report the rid so the router re-routes.
            if shm_path is not None:
                self._unlink(shm_path)
            with self._feed_lock:
                self._failed.append((rid, repr(e)))
            return
        self.prefills += 1
        with self._feed_lock:
            self._done.append((rid, "handoff"))

    # -- transport helpers ---------------------------------------------------
    def _segment_store(self):
        if self._store is None:
            from ray_lightning_tpu.cluster.shm import SegmentStore

            self._store = SegmentStore(prefix=KV_SEGMENT_PREFIX)
        return self._store

    def _put(self, addr: Tuple[str, int], item: Dict[str, Any]) -> None:
        self._out.put(addr, item)

    @staticmethod
    def _unlink(path: str) -> None:
        import os

        try:
            os.unlink(path)
        except OSError:
            pass

    def _prune_segments(self, now: float) -> None:
        """TTL janitor for handoffs whose replica died between send and
        read — the pid-based sweep cannot collect them (this producer
        is alive); the TTL can."""
        with self._feed_lock:  # work thread appends concurrently
            expired = [p for p, t in self._live_segments
                       if now - t > self._segment_ttl_s]
            self._live_segments = [
                (p, t) for p, t in self._live_segments
                if now - t <= self._segment_ttl_s
            ]
        for path in expired:
            self._unlink(path)

    def _maybe_beat(self, force: bool = False,
                    closing: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last_beat < self.beat_s:
            return
        self._last_beat = now
        self._prune_segments(now)
        try:
            # Before the feed drain: a blackholed beat loses nothing —
            # the next beat carries the same done/failed entries.
            _fault_fire("beat")
        except FaultBlackhole:
            return
        with self._feed_lock:
            done, self._done = self._done, []
            failed, self._failed = self._failed, []
        try:
            self._beat_handle.put(make_beat_item(
                "prefill", self.worker_id, done=done, failed=failed,
                adapters=(None if self.adapters is None
                          else self.adapters.names()),
                closing=closing,
            ))
        except (OSError, ConnectionError):
            # Router gone (shutting down); keep draining dispatches.
            with self._feed_lock:
                self._done, self._failed = done + self._done, \
                    failed + self._failed

    def close(self, consume_grace_s: float = 5.0) -> None:
        self._inbox.shutdown()
        self._out.close()
        if self.prefix is not None:
            self.prefix.drop_all()
        if self._trace_dir is not None and self.tracer.events():
            import os

            try:
                os.makedirs(self._trace_dir, exist_ok=True)
                self.tracer.export_jsonl(
                    f"{self._trace_dir}/trace-prefill-"
                    f"{self.worker_id}.jsonl"
                )
            except OSError:
                pass  # a full disk must not fail the teardown
        if self._store is None:
            return
        # A handoff already DELIVERED to a busy replica's inbox may not
        # be read yet — unlinking it now would turn an accepted request
        # into a terminal "invalid" on a planned scale-down.  The
        # consumer unlinks on read, so wait out a short grace for the
        # tracked segments to disappear before reclaiming leftovers
        # (a replica that never reads within the grace is the dead-
        # handoff case the TTL/sweep janitors exist for anyway).
        import os

        deadline = time.monotonic() + consume_grace_s
        while time.monotonic() < deadline:
            with self._feed_lock:
                paths = [p for p, _ in self._live_segments]
            if not any(os.path.exists(p) for p in paths):
                break
            time.sleep(0.05)
        self._store.unlink_all()
