"""The serve loop: compiled programs + continuous batching + SLO stats.

Steady-state shape discipline (the whole point): after warmup the
engine dispatches exactly TWO program families —

* one **prefill program per bucket length** (a handful, compiled on
  first use of each bucket);
* ONE **fixed-width decode program** over the ``num_slots`` slot set.

Join-on-arrival, evict-on-finish, growth and preemption all happen
host-side between steps by mutating the programs' int32 operands
(block tables, sequence lengths, current tokens) — never a shape, so
steady-state serving triggers ZERO recompiles (asserted by the bench
and the serve test suite via the telemetry recompile counter).

The engine is driver-side and single-threaded over the device: call
:meth:`step` yourself (tests, bench inner loops) or :meth:`start` a
background thread (`serve_forever` semantics).  Requests arrive either
in-process (:meth:`submit`) or over the DriverQueue plane
(:meth:`queue_handle` + ``serve/client.py``) — same admission path,
same backpressure.

Disaggregated mode (``serve/dist/``): the inbox also accepts
``serve_kv_handoff`` items — a request a PREFILL WORKER already ran,
its per-layer KV blocks and final-position logits riding the queue
plane.  Admission then scatters the blocks into this engine's own pool
(``kv_cache.import_blocks`` — one compiled program per bucket block
count, like the prefill set) and samples the first token from the
shipped logits, so the request goes straight to the fixed-width
decode/verify programs with ZERO extra recompiles.  Wire requests may
also PRESET ``sample_seed`` (the router's fleet-wide submission
ordinal) so a failover re-submission to a different replica replays
the identical sampling stream.
"""

from __future__ import annotations

import contextlib
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_lightning_tpu.fault.inject import (
    FaultBlackhole, FaultInjected, fire as _fault_fire, set_member,
)
from ray_lightning_tpu.ops.paged_attention import walk_plan
from ray_lightning_tpu.serve.metrics import LoopWatch, ServeStats
from ray_lightning_tpu.telemetry.propagate import (
    child_context, trace_args,
)
from ray_lightning_tpu.telemetry.runtime import TelemetryConfig
from ray_lightning_tpu.telemetry.spans import SpanTracer, phase
from ray_lightning_tpu.telemetry.step_stats import compile_event_count

__all__ = ["ServeConfig", "ServeEngine", "ServeHandle", "ServeRejected"]


class ServeRejected(RuntimeError):
    """Admission backpressure: the queue is full (or the request
    expired before admission).  Typed so clients can retry-with-backoff
    without string-matching."""


@dataclass(frozen=True)
class ServeConfig:
    """Serving knobs (docs/SERVING.md "Knobs")."""

    # Decode width: concurrent sequences in flight.  The ONE decode
    # program is compiled at this width; admissions only fill slots.
    num_slots: int = 8
    # Tokens per KV block.  Smaller = finer pool granularity, larger =
    # fewer scatter/gather indices per sequence.
    block_size: int = 16
    # Physical blocks in the pool (block 0 is the trash block).  None =
    # enough for every slot at max_model_len plus one admission's worth
    # of headroom — preemption-free at full width.
    num_blocks: Optional[int] = None
    # Longest prompt+generation the engine admits.  None = the model's
    # positional table (cfg.seq_len).
    max_model_len: Optional[int] = None
    # Prefill bucket lengths (multiples of block_size).  None =
    # power-of-two block counts up to max_model_len.
    prefill_buckets: Optional[Sequence[int]] = None
    # Admission-queue bound: submissions beyond it are REJECTED
    # synchronously (backpressure, never silent queue bloat).
    max_queue: int = 64
    # Multi-tenant LoRA multiplexing (serve/lora.py): capacity of the
    # resident adapter pool (0 = no pool — the engine's program set is
    # byte-identical to pre-LoRA rounds) and the stacked-buffer rank
    # every loaded adapter must match.  Adapters ride every dispatch
    # as a per-slot int32 OPERAND, so any tenant mix shares the
    # compiled-once program set (zero steady-state recompiles).
    max_adapters: int = 0
    adapter_rank: int = 0
    # Per-tenant admission bound: one adapter's burst beyond it is
    # REJECTED while other tenants keep their queue seats (None = the
    # shared max_queue only).
    max_queue_per_adapter: Optional[int] = None
    # Speculative decoding: default drafted tokens per tick when a
    # draft model is loaded (the verify program's width is spec_k + 1).
    # Requires draft_module/draft_params at engine build; per-request
    # ``spec=`` overrides downward (0 = plain target decode).
    spec_k: int = 0
    # Prefix-aware KV reuse (kv_cache.PrefixIndex): resident prompt
    # chains stay in the pool after their requests finish, and a new
    # request's prefill skips every whole block it shares with one —
    # the shared prefix is claimed by refcount bumps (zero device
    # work), only the uncovered suffix is computed.  False keeps the
    # engine byte-identical to pre-cache rounds.
    prefix_cache: bool = False
    # Chunked prefill width (tokens, a multiple of block_size): prompts
    # whose uncovered suffix exceeds it are prefilled one fixed-width
    # chunk per engine step, interleaved with decode ticks, so a long
    # prompt never head-of-line-blocks the resident decode slots — and
    # prompts past the largest prefill bucket become admissible (up to
    # max_model_len).  None = whole-prompt bucketed prefill only.
    prefill_chunk: Optional[int] = None
    # Sampling seed for temperature>0 requests.
    seed: int = 0
    # The next two are what the engine does, not options: they are kept
    # for the tests' reference path (``False`` is the serial loop with a
    # frame a token; served tokens are equal on both) and go with the
    # next ``benchmark`` PR that drops the two keys from
    # ``benchmarks/workloads/k-exaone-236b-a23b-ep8.serve-mixed.json``
    # (ROADMAP.md D3).
    # One reply frame a tick per reply address (``serve_batch``, the
    # tick's ``serve_token`` / ``serve_done`` items in order; a tick with
    # one item for an address sends the bare item).
    coalesce_replies: bool = True
    # The decode loop runs one tick ahead of the host: on a tick whose
    # slots all go on, the next decode is dispatched on this one's tokens
    # where they lie on the device, before they are fetched; where an
    # active request carries an ``eos_token_id``, after the fetch and
    # before the replies.  An engine with a draft model, an adapter pool,
    # ``prefix_cache`` or ``prefill_chunk`` books state between ticks
    # that this path does not handle and runs the serial loop.
    decode_lookahead: bool = True
    # Background-thread idle sleep between polls when no work exists.
    idle_wait_s: float = 0.002
    # Live-export refresh cadence (prom textfile / serve-live.json).
    export_every_s: float = 1.0
    # Fleet SLO & capacity plane (docs/OBSERVABILITY.md "SLO, burn
    # rate & capacity"): the headroom oracle (serve/capacity.py) and
    # the burn-rate evaluator (telemetry/slo.py) tick on the export
    # cadence.  OFF by default — disabled engines keep snapshot() and
    # serve-live.json byte-identical to pre-plane rounds.
    capacity: bool = False
    slo: bool = False
    # Time-series bin width for the plane's store (RLT_TS_INTERVAL_S).
    ts_interval_s: float = 1.0
    # Queue-wait bound (ms) for the stock serve_queue_wait SLO.
    slo_queue_wait_ms: float = 500.0
    # Override the stock SLOs' (fast_s, slow_s, burn-bound) window
    # pairs.  None = telemetry/slo.py defaults (minutes-scale);
    # benches shrink them to their arm horizons.
    slo_windows: Optional[Tuple[Tuple[float, float, float], ...]] = None


class ServeHandle:
    """Host-side future for one request."""

    def __init__(self, rid: str, request):
        self.rid = rid
        self.request = request
        self.error: Optional[BaseException] = None  # engine-death only
        self._done = threading.Event()

    @property
    def status(self) -> str:
        return self.request.state.value

    @property
    def tokens(self) -> List[int]:
        return list(self.request.generated)

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Generated tokens (prompt excluded).  Raises
        :class:`ServeRejected` on backpressure/expiry, ``TimeoutError``
        when the engine did not finish in time."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} not finished within {timeout}s "
                f"(state={self.status})"
            )
        if self.error is not None:
            raise RuntimeError(
                f"serve engine died with request {self.rid} in flight"
            ) from self.error
        if self.request.done_reason in ("rejected", "expired"):
            raise ServeRejected(
                f"request {self.rid} {self.request.done_reason}"
            )
        return list(self.request.generated)


@dataclass
class _PrefillJob:
    """One chunked prefill in flight (engine-internal): the request,
    its private block-table row (the scheduler row stays trashed until
    the last chunk lands), and the first prompt position not yet
    written."""

    req: Any
    row: Any
    next_pos: int


class ServeEngine:
    """Continuous-batching inference engine for one GPT module."""

    def __init__(self, module, params, config: Optional[ServeConfig] = None,
                 telemetry_dir: Optional[str] = None,
                 prom_file: Optional[str] = None,
                 prom_port: Optional[int] = None,
                 draft_module=None, draft_params=None,
                 trace_dir: Optional[str] = None,
                 trace_name: Optional[str] = None,
                 adapters: Optional[Dict[str, dict]] = None):
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.models.generate import _reject_unmerged_lora
        from ray_lightning_tpu.models.quant import (
            dequantize_decode_params, is_quantized,
        )
        from ray_lightning_tpu.serve.kv_cache import (
            GPTServeFamily, PagedKVCache,
        )
        from ray_lightning_tpu.serve.scheduler import (
            Scheduler, derive_geometry,
        )

        def _prep(tree, family, c):
            """The tree the programs are called with, and how many of
            its leaves changed dtype on the way.  The caller's tree is
            not kept: it can be dropped after ``ServeEngine(...)``."""
            tree = jax.tree.map(jnp.asarray, tree)
            # Same backend gate as generate(): off-TPU, per-token
            # dequant inside the decode program costs more than the
            # weight-bandwidth it saves — hoist it once at engine build.
            if is_quantized(tree) and jax.default_backend() != "tpu":
                tree = dequantize_decode_params(tree)
            # The family whose programs read the tree holds it in the
            # dtype they read it in: no weight is converted per call.
            # (By path: a family may also rearrange a leaf into others.)
            was = {path: leaf.dtype for path, leaf in
                   jax.tree_util.tree_leaves_with_path(tree)}
            tree = family.prepare_params(tree, c)
            return tree, sum(
                was.get(path, leaf.dtype) != leaf.dtype for path, leaf in
                jax.tree_util.tree_leaves_with_path(tree))

        self.module = module
        self.cfg = module.config
        self.config = cfg = config or ServeConfig()
        # The one seam to the model family: pool layout, prefill and
        # decode programs.  A module without ``serve_family`` is GPT,
        # served by serve/kv_cache.py's functions as it always was.
        self.family = (module.serve_family()
                       if hasattr(module, "serve_family")
                       else GPTServeFamily(self.cfg))
        # A family says itself what it cannot serve (``refuses``, with
        # its reason; GPT's is empty).
        refused = [
            text for key, text, on in (
                ("prefix_cache", "prefix_cache", cfg.prefix_cache),
                ("spec_k", "spec_k > 0", cfg.spec_k > 0),
                ("draft", "a draft model", draft_module is not None),
                ("adapters", "LoRA adapters (max_adapters / adapters=)",
                 cfg.max_adapters > 0 or bool(adapters)),
                ("prefill_chunk", "prefill_chunk",
                 cfg.prefill_chunk is not None),
            ) if on and key in self.family.refuses]
        if refused:
            raise ValueError(
                f"the {self.family.name} family does not support: "
                f"{', '.join(refused)} ({self.family.refuses_why})"
            )
        _reject_unmerged_lora(params)
        self._c = module._compute_dtype()
        self.params, cast_leaves = _prep(params, self.family, self._c)
        if (draft_module is None) != (draft_params is None):
            raise ValueError(
                "draft_module and draft_params come as a pair"
            )
        if cfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {cfg.spec_k}")
        if cfg.spec_k > 0 and draft_module is None:
            raise ValueError(
                "spec_k > 0 needs a draft model: pass draft_module/"
                "draft_params (serve/draft.py builds one from the "
                "target)"
            )
        if draft_module is not None and cfg.spec_k < 1:
            raise ValueError(
                "a draft model without spec_k >= 1 would never be "
                "consulted — set ServeConfig(spec_k=K)"
            )
        # Multi-tenant LoRA: the resident adapter pool (None = no
        # multiplexing; every program stays byte-identical to
        # pre-LoRA rounds).  Base params stay lora-FREE either way —
        # _reject_unmerged_lora above guards the truly-unsupported
        # case (adapters smuggled in as the base tree).
        self.adapters = None
        if cfg.max_adapters > 0:
            from ray_lightning_tpu.serve.lora import AdapterPool

            if cfg.adapter_rank < 1:
                raise ValueError(
                    "max_adapters > 0 needs adapter_rank >= 1 (the "
                    "stacked-buffer rank every adapter shares)"
                )
            self.adapters = AdapterPool(
                self.cfg, cfg.max_adapters, cfg.adapter_rank,
                dtype=self._c,
            )
            for name, adapter in (adapters or {}).items():
                self.adapters.add(name, adapter)
        elif adapters:
            raise ValueError(
                "adapters= passed but ServeConfig.max_adapters is 0 — "
                "size the pool (max_adapters/adapter_rank) to serve "
                "multi-tenant LoRA"
            )
        self.draft_module = draft_module
        self.draft_params = None
        if draft_module is not None:
            if draft_module.config.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft_module.config.vocab_size}) != "
                    f"target vocab ({self.cfg.vocab_size}) — drafted "
                    f"tokens would not be target tokens"
                )
            _reject_unmerged_lora(draft_params)
            self._draft_c = draft_module._compute_dtype()
            # The draft's programs are GPT's (serve/kv_cache.py).
            self.draft_params, _ = _prep(
                draft_params, GPTServeFamily(draft_module.config),
                self._draft_c)
        self.spec_k = cfg.spec_k if draft_module is not None else 0

        if (cfg.max_model_len or 0) > self.cfg.seq_len:
            raise ValueError(
                f"max_model_len {cfg.max_model_len} exceeds the "
                f"positional table ({self.cfg.seq_len})"
            )
        # Shared derivation rule (scheduler.derive_geometry): prefill
        # workers run the SAME function, so handoff geometry can never
        # drift between a worker and its replicas.
        self.max_model_len, buckets = derive_geometry(cfg, self.cfg)
        blocks_per_seq = -(-self.max_model_len // cfg.block_size)
        num_blocks = cfg.num_blocks
        if num_blocks is None:
            # Preemption-free at full width: every slot at max length,
            # one extra admission's worth of blocks, plus the trash
            # block.
            num_blocks = (cfg.num_slots + 1) * blocks_per_seq + 1
        if num_blocks - 1 < blocks_per_seq:
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold even one "
                f"max-length sequence ({blocks_per_seq} blocks)"
            )
        self.cache = self.family.make_cache(
            num_blocks, cfg.block_size, cfg.num_slots, self._c
        )
        # The longest RETAINED bucket bounds the admissible prompt
        # length — submit() enforces it, so Scheduler.bucket_for can
        # never raise inside the serve loop.
        self.max_prompt_len = buckets[-1]
        self.scheduler = Scheduler(
            cfg.num_slots, self.cache.allocator, cfg.block_size,
            blocks_per_seq, buckets, max_queue=cfg.max_queue,
            max_queue_per_adapter=cfg.max_queue_per_adapter,
            window_allocator=getattr(self.cache, "window_allocator", None),
            window_blocks=getattr(self.cache, "window_blocks", 0),
        )
        # Prefix-aware KV reuse + chunked prefill (docs/SERVING.md
        # "Prefix caching & chunked prefill").  All host-side wiring:
        # the claim hands the scheduler refcount-bumped block ids, the
        # reclaim hook lets pool pressure evict resident chains before
        # any running request is preempted, and chunk_width routes
        # long-suffix admissions to exact block coverage.
        self._chunk = None
        if cfg.prefill_chunk is not None:
            self._chunk = int(cfg.prefill_chunk)
            if self._chunk < cfg.block_size \
                    or self._chunk % cfg.block_size:
                raise ValueError(
                    f"prefill_chunk {cfg.prefill_chunk} must be a "
                    f"positive multiple of block_size {cfg.block_size}"
                )
            if self._chunk > self.max_model_len:
                raise ValueError(
                    f"prefill_chunk {cfg.prefill_chunk} exceeds "
                    f"max_model_len {self.max_model_len}"
                )
            self.scheduler.chunk_width = self._chunk
        self.prefix_cache = None
        if cfg.prefix_cache:
            from ray_lightning_tpu.serve.kv_cache import PrefixIndex

            self.prefix_cache = PrefixIndex(
                self.cache.allocator, cfg.block_size
            )
            self.scheduler.claim_fn = self._claim_prefix
            self.scheduler.reclaim = self.prefix_cache.evict
        # In-flight chunked prefills, keyed by slot.  While a job runs,
        # the slot's scheduler row points at the trash block and its
        # seq_len is 0 — the decode program treats it exactly like an
        # inactive slot (writes trashed, sampled token ignored), so the
        # job needs no change to the compiled decode graph.
        self._chunk_jobs: Dict[int, "_PrefillJob"] = {}
        # Depth of the decode loop against the host, from the engine's
        # build: 1 (``_decode_tick`` dispatches a tick ahead where the
        # tick's slots allow it) unless the engine books state between
        # ticks that a decode in flight would not see.
        self._pipelined = cfg.decode_lookahead and not (
            draft_module is not None or cfg.max_adapters > 0
            or cfg.prefix_cache or cfg.prefill_chunk is not None)
        # The decode dispatched ahead of its tick, as ``_dispatch_decode``
        # returned it, or None; and when the last tick's tokens reached
        # the host (``time.monotonic()``): a decode dispatched behind
        # another starts on the device no earlier than that.
        self._ahead: Optional[tuple] = None
        self._fetched_t = 0.0
        # Admissions of this iteration whose first token is not fetched
        # yet, in the order their prefills were dispatched: ``(slot,
        # request, the token on the device, the request's open span, host
        # seconds its dispatch took)``.  Empty between iterations.
        self._firsts: List[tuple] = []
        # Adapter names whose cached chains must be dropped before the
        # next admission poll: add/remove_adapter run on OTHER threads,
        # and every PrefixIndex mutation belongs to the serve thread —
        # so they queue the invalidation here (under self._lock) and
        # step() drains it under the SAME lock hold as poll(), which
        # orders the drop strictly before any claim against the new
        # factors.
        self._prefix_drops: List[str] = []
        self.stats = ServeStats()
        # Set once: the bytes of the target's tree as the programs are
        # called with it, and the leaves whose dtype the preparation
        # changed (0 where the tree came in the dtype it is read in).
        self.stats.bump_many({
            "weights_resident_bytes": sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.params)),
            "weights_cast_leaves": cast_leaves,
        })
        # Latent layers (a family whose cache row is one compressed
        # latent a position, shared by all heads): how many, and the
        # bytes of data in one cached position of one of them.
        self._n_latent = getattr(self.family, "n_latent", 0)
        if self._n_latent:
            self.stats.bump(
                "latent_row_bytes", self.family.latent_row_bytes(self._c))
        self._pool = self.cache.init_pool()
        self._walk_layers = self.family.walk_layers(self._pool)
        # Sliding layers whose decode runs the ring kernel (two-kind).
        self._ring_layers = (self.family.ring_layers(self._pool)
                             if self.family.two_kind else 0)
        self._draft_pool = None
        if draft_module is not None:
            dcfg = draft_module.config
            if dcfg.seq_len < self.max_model_len:
                raise ValueError(
                    f"draft positional table ({dcfg.seq_len}) shorter "
                    f"than max_model_len ({self.max_model_len})"
                )
            # The draft pool mirrors the target pool's block geometry
            # (same num_blocks, same block_size) and SHARES the slot
            # block tables — one allocator, one coverage/rollback
            # arithmetic, two pools.
            self._draft_cache = PagedKVCache(
                dcfg, num_blocks, cfg.block_size, dtype=self._draft_c
            )
            self._draft_pool = self._draft_cache.init_pool()
        self._cur_tokens = np.zeros((cfg.num_slots,), np.int32)
        self._started_t = time.monotonic()
        # Request-scoped distributed tracing (docs/OBSERVABILITY.md
        # "Distributed tracing"): wall-clock spans per critical-path
        # phase, exported as trace-serve-<name>.jsonl at stop() for
        # telemetry/trace_collect.py to stitch.  OFF unless trace_dir
        # is set.  The loop's own phases (PHASES["serve"]) are spans
        # in the same ring; profiler annotations and tick_<phase>_us
        # counters they are always.
        self._trace_dir = trace_dir
        self._trace_name = trace_name or uuid.uuid4().hex[:6]
        self.tracer = SpanTracer(
            enabled=trace_dir is not None, maxlen=16384, rank=0,
            clock=time.time,
        )
        self._tick_us: Dict[str, int] = {}  # this turn's phases
        # The loop's turns chain: ``between`` is opened on the clock read
        # that closes a turn and closed on the one that opens the next
        # (``_open_turn`` / ``_close_turn``), so the phases tile the
        # thread's wall; None before the first turn and after the loop
        # was left (``_drop_between``).  With it the thread that closed
        # the turn, and the engine's facts of the turn in hand for a
        # stall's record: active slots, the admissions' buckets, the
        # decode tick's counts.
        self._between = None
        self._turn_thread = 0
        self._turn_slots = 0
        self._turn_buckets: Sequence[int] = ()
        self._turn_counts: Dict[str, int] = {}
        self._compiles_seen = compile_event_count()   # arms the listener
        # What the loop's thread was doing, and a record of every turn
        # that stalled (``serve/metrics.py`` ``LoopWatch``): at every
        # telemetry tier but ``off`` (``RLT_TELEMETRY``, as a fit reads
        # it).
        self._watch = None
        if TelemetryConfig.coerce(None).tier != "off":
            self._watch = LoopWatch()
        self._build_programs()

        self._handles: Dict[str, ServeHandle] = {}  # guarded by self._lock
        # Terminal (rid, status) pairs since the last drain_done() —
        # the completion feed a disaggregated replica's beats carry so
        # the router can prune its in-flight tracking.  Bounded: an
        # undreained feed (no router) must never grow without bound.
        self._done_feed: deque = deque(maxlen=4096)  # guarded by self._lock
        # Non-terminal (rid, error) handoff-admission failures — fed to
        # the router by replica beats (``failed`` key) so it re-routes
        # the PREFILL instead of failing the request terminally.  Only
        # populated when a replica runner opts in below.
        self._failed_feed: deque = deque(maxlen=4096)  # guarded by self._lock
        # Disaggregated-replica mode: a torn/vanished handoff payload
        # becomes a beat-reported retryable failure (router re-routes
        # the prefill) instead of a terminal ``invalid`` reply.  The
        # replica runner flips this on; a standalone queue-plane engine
        # keeps the terminal-reply behavior.
        self.report_handoff_failures = False
        # Serve-fleet identity for the fault grammar: the runner sets
        # ("decode", replica_id) so the serve THREAD (started later,
        # from start()) can declare itself to the thread-local member
        # context in fault/inject.py.
        self.fault_member: Optional[Tuple[str, str]] = None
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._inbox = None           # DriverQueue, lazily created
        # Handoffs whose tenant's serve_adapter_load frame has not
        # landed yet (the worker's handoff rides its OWN connection and
        # can outrun the router's load frame): re-tried each drain for
        # a bounded number of cycles before the typed-invalid fallback.
        # Serve-loop-thread only — never shared, no lock.
        self._deferred_inbox: deque = deque()
        # Serve-thread send cache; stop() closes it from the
        # caller's thread after a join(timeout) that a wedged
        # dispatch can outlive — so it shares the lock.
        # guarded by self._lock
        self._reply_handles: Dict[Tuple[str, int], Any] = {}
        # Open only inside a tick's emit loop: (owner thread, replies by
        # address, in order).
        self._reply_batch: Optional[
            Tuple[int, Dict[Tuple[str, int], List[dict]]]] = None
        self._exporter = None
        self._live_path = None
        self._last_export = 0.0
        if prom_file or prom_port is not None:
            from ray_lightning_tpu.telemetry.export_prom import PromExporter

            self._exporter = PromExporter(
                textfile=prom_file, port=prom_port
            )
        if telemetry_dir:
            import os

            os.makedirs(telemetry_dir, exist_ok=True)
            self._live_path = f"{telemetry_dir}/serve-live.json"
        # Fleet SLO & capacity plane: headroom oracle + burn-rate
        # evaluator, ticked by _maybe_export on the export cadence —
        # host-side dict folds only, zero new device work, so the
        # recompile counter stays pinned with the plane on.
        self._capacity = None
        self._slo = None
        self._slo_alerts: deque = deque(maxlen=256)
        if cfg.capacity or cfg.slo:
            from ray_lightning_tpu.serve.capacity import CapacityOracle

            self._capacity = CapacityOracle(
                interval_s=cfg.ts_interval_s, clock=time.time,
            )
            # Derived capacity snapshots (model fit + trends over
            # every series) refresh at ~1 Hz no matter how fast the
            # export tick runs; beats and exports reuse the cached
            # result in between.
            self._capacity_every_s = max(cfg.export_every_s, 1.0)
            self._last_capacity = 0.0
        if cfg.slo:
            import dataclasses

            from ray_lightning_tpu.telemetry.slo import (
                SloEvaluator, default_serve_slos,
            )

            specs = default_serve_slos(cfg.slo_queue_wait_ms)
            if cfg.slo_windows is not None:
                windows = tuple(tuple(w) for w in cfg.slo_windows)
                specs = tuple(
                    dataclasses.replace(s, windows=windows)
                    for s in specs
                )
            self._slo = SloEvaluator(
                self._capacity.store, specs,
                clock=time.time, emit=self._slo_alerts.append,
            )

    # -- compiled programs ---------------------------------------------------
    def _build_programs(self) -> None:
        import jax
        import jax.numpy as jnp

        from ray_lightning_tpu.serve.kv_cache import (
            import_blocks, make_slot_keys, paged_decode_step,
            paged_prefill, paged_verify_step, sample_tokens,
        )
        from ray_lightning_tpu.telemetry.program_ledger import ledgered_jit

        cfg, c, fam = self.cfg, self._c, self.family
        base_key = jax.random.PRNGKey(self.config.seed)
        # Donation keeps the pool update in place on TPU; XLA:CPU cannot
        # donate and would warn on every dispatch.
        donate = (1,) if jax.default_backend() == "tpu" else ()
        # Multi-tenant LoRA: the BGMV arm is resolved ONCE here (probe
        # or RLT_LORA_BGMV), then closed over — never re-decided on the
        # dispatch path.  Pool-less engines trace with adapters=None,
        # keeping their graphs byte-identical to pre-LoRA rounds.
        lora_impl = self.adapters.impl if self.adapters is not None \
            else "xla"

        def _decode(params, pool, block_tables, seq_lens, tokens, temps,
                    seeds, top_ks, ad, ad_ids):
            # (logits, pool) and, from a family that counts them, the
            # program's own integer sums (routed assignments): handed
            # on beside the tokens.
            logits, pool, *sums = fam.decode(
                params, pool, block_tables, seq_lens, tokens,
                compute_dtype=c, adapters=ad, adapter_ids=ad_ids,
                lora_impl=lora_impl,
            )
            keys = make_slot_keys(base_key, seeds, seq_lens)
            return (sample_tokens(logits, keys, temps, top_ks), pool, *sums)

        def _prefill(params, pool, tokens, prompt_len, block_ids, temp,
                     seed, top_k, ad, ad_id):
            logits, pool, *sums = fam.prefill(
                params, pool, tokens, prompt_len, block_ids,
                compute_dtype=c, adapters=ad, adapter_id=ad_id,
                lora_impl=lora_impl,
            )
            keys = make_slot_keys(
                base_key, seed[None], (prompt_len - 1)[None]
            )
            first = sample_tokens(
                logits[None], keys, temp[None], top_k[None]
            )[0]
            return (first, pool, *sums)

        def _first(logits, prompt_len, temp, seed, top_k):
            # Disaggregated admission: the prefill worker shipped the
            # final-position logits with the KV blocks; sampling them
            # HERE with this engine's keys is bitwise the tail of
            # _prefill — local and imported admissions emit identical
            # first tokens.
            keys = make_slot_keys(
                base_key, seed[None], (prompt_len - 1)[None]
            )
            return sample_tokens(
                logits[None], keys, temp[None], top_k[None]
            )[0]

        self._decode_fn = ledgered_jit(
            _decode, site="serve/decode", donate_argnums=donate
        )
        # One python callable; XLA compiles one executable per bucket
        # length (tokens/block_ids shapes) — the bucketed prefill set
        # lands in the program ledger as one site with a variant per
        # bucket, each lowered under a name that carries its bucket
        # (``jit__prefill_b<bucket>`` on a device trace).
        self._prefill_fn = ledgered_jit(
            _prefill, site="serve/prefill", donate_argnums=donate,
            name_of=lambda params, pool, tokens, *_:
                f"_prefill_b{tokens.shape[0]}",
        )
        # Disaggregated KV import: one executable per bucket block
        # count (block_ids shape), mirroring the prefill set — fleet
        # warmup compiles them all, steady state never recompiles.
        self._import_fn = ledgered_jit(
            import_blocks, site="serve/kv_import",
            donate_argnums=(0,) if jax.default_backend() == "tpu" else (),
        )
        self._first_fn = ledgered_jit(_first, site="serve/first_token")

        def _feed(tokens, slot, first):
            # An admission's first token written into the next decode's
            # ``tokens`` operand where both lie on the device.  The slot
            # is a traced scalar and the program is applied once an
            # admission: one shape, however many admissions a tick.
            return tokens.at[slot].set(first)

        self._feed_fn = ledgered_jit(_feed, site="serve/feed_first")

        def _chunk_prefill(params, pool, table_row, start, tokens, limit,
                           sample_idx, temp, seed, top_k, ad, ad_ids):
            # One prompt chunk through the verify program at W=1: the
            # window writes k/v at positions start + [0, Tc) into the
            # slot's blocks (write_limit trashes the padding tail) and
            # attends under the same causal frontier the bucketed
            # prefill enforces — so a prompt computed suffix-only over
            # claimed prefix blocks, or chunk by chunk, fills the cache
            # with the same values.  ``sample_idx`` picks the window
            # position whose logits produce the first token (the final
            # chunk passes prompt_len - 1 - start; earlier chunks pass
            # 0 and ignore the token) with the request's position-keyed
            # stream — bitwise the tail of _prefill.
            logits, pool = paged_verify_step(
                cfg, params, pool, table_row, start, tokens, limit,
                compute_dtype=c, adapters=ad, adapter_ids=ad_ids,
                lora_impl=lora_impl,
            )
            pick = jax.lax.dynamic_index_in_dim(
                logits[0], sample_idx, axis=0, keepdims=False
            )
            keys = make_slot_keys(
                base_key, seed[None], (start[0] + sample_idx)[None]
            )
            tok = sample_tokens(
                pick[None], keys, temp[None], top_k[None]
            )[0]
            return tok, pool

        # Compiled per chunk width: the fixed prefill_chunk width for
        # jobs plus one per bucket used by inline suffix computes — a
        # bounded set, warmed on first use like the prefill buckets.
        self._chunk_fn = ledgered_jit(
            _chunk_prefill, site="serve/chunk_prefill",
            donate_argnums=donate,
        )

        if self.draft_module is None:
            return
        dcfg, dc = self.draft_module.config, self._draft_c
        K = self.spec_k

        def _draft_prefill(dparams, dpool, tokens, prompt_len, block_ids):
            _, dpool = paged_prefill(
                dcfg, dparams, dpool, tokens, prompt_len, block_ids,
                compute_dtype=dc,
            )
            return dpool

        def _draft_step(dparams, dpool, block_tables, positions, prev,
                        override, use_override, limits):
            # The chain's token source is resolved ON DEVICE so the
            # K+1 dispatches never round-trip to the host: dispatch 0
            # feeds the host-provided start token, dispatch 1 feeds the
            # current token on slots that spent dispatch 0 syncing the
            # bonus-token position, everything later feeds the previous
            # dispatch's own greedy proposal.
            tokens = jnp.where(use_override, override, prev)
            logits, dpool = paged_decode_step(
                dcfg, dparams, dpool, block_tables, positions, tokens,
                compute_dtype=dc, write_limit=limits,
            )
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), dpool

        def _verify(params, pool, block_tables, seq_lens, tokens, limits,
                    temps, seeds, top_ks, ad, ad_ids):
            logits, pool = paged_verify_step(
                cfg, params, pool, block_tables, seq_lens, tokens,
                limits, compute_dtype=c, adapters=ad,
                adapter_ids=ad_ids, lora_impl=lora_impl,
            )
            W, T = tokens.shape
            pos = (seq_lens[:, None] + jnp.arange(T)).reshape(-1)
            keys = make_slot_keys(
                base_key, jnp.repeat(seeds, T), pos
            )
            sampled = sample_tokens(
                logits.reshape(W * T, -1), keys,
                jnp.repeat(temps, T),
                None if top_ks is None else jnp.repeat(top_ks, T),
            )
            return sampled.reshape(W, T), pool

        def _draft_chunk(dparams, dpool, table_row, start, tokens, limit):
            # The draft-pool mirror of _chunk_prefill: same window, same
            # blocks (the draft cache shares the slot block tables), so
            # a claimed/chunked admission leaves the draft frontier
            # exactly where a bucketed _draft_prefill would have.
            _, dpool = paged_verify_step(
                dcfg, dparams, dpool, table_row, start, tokens, limit,
                compute_dtype=dc,
            )
            return dpool

        self._draft_prefill_fn = ledgered_jit(
            _draft_prefill, site="serve/draft_prefill",
            donate_argnums=donate,
        )
        self._draft_step_fn = ledgered_jit(
            _draft_step, site="serve/draft_step", donate_argnums=donate
        )
        self._draft_chunk_fn = ledgered_jit(
            _draft_chunk, site="serve/draft_chunk", donate_argnums=donate
        )
        self._verify_fn = ledgered_jit(
            _verify, site="serve/verify", donate_argnums=donate
        )
        self._spec_width = K + 1

    # -- submission ----------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               temperature: float = 0.0,
               eos_token_id: Optional[int] = None,
               top_k: Optional[int] = None,
               spec: Optional[int] = None,
               adapter: Optional[str] = None,
               deadline_s: Optional[float] = None,
               sample_seed: Optional[int] = None,
               on_token=None, rid: Optional[str] = None,
               _handoff: Optional[dict] = None,
               _trace_ctx=None, _recv_t=None) -> ServeHandle:
        """Enqueue one request (thread-safe).  Returns a handle; a
        backpressure rejection is visible immediately as
        ``handle.status == "rejected"`` (and ``result()`` raises).

        ``spec`` caps this request's speculative draft count: None =
        the engine's ``spec_k`` default, 0 = plain target decode, K =
        at most K drafted tokens verified per tick (clamped to the
        engine width).

        ``adapter`` decodes this request through the named tenant's
        LoRA adapter (the pool's per-slot gathered delta, slot 0 for
        None) — unknown or pool-less names are typed ``ValueError``
        rejections (the queue plane surfaces them as ``invalid``
        replies), never silent base-model fallbacks.

        ``sample_seed`` presets the request's sampling-stream identity
        (None = this engine's submission ordinal).  The disaggregated
        router assigns fleet-wide seeds so re-submitting a failed-over
        request to ANY replica replays the identical token stream.

        ``_handoff`` (internal, ``serve/dist/``) carries a prefill
        worker's exported KV payload — admission imports it instead of
        running the local prefill program.  ``_recv_t`` (internal, the
        queue plane) is when the request's frame landed in the inbox
        (``time.monotonic()``); only ``queue_wait_us`` counts from it."""
        from ray_lightning_tpu.serve.scheduler import Request

        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if top_k is not None:
            top_k = int(top_k)
            if top_k < 1:
                raise ValueError(f"top_k must be >= 1, got {top_k}")
            if temperature <= 0.0:
                raise ValueError(
                    "top_k requires temperature > 0 (temperature=0 is "
                    "greedy decoding, which would silently ignore it)"
                )
        if spec is not None:
            spec = int(spec)
            if spec < 0:
                raise ValueError(f"spec must be >= 0, got {spec}")
            if spec > 0 and self.draft_module is None:
                raise ValueError(
                    "spec > 0 on an engine without a draft model — "
                    "build the ServeEngine with draft_module/draft_params"
                )
        if sample_seed is not None:
            sample_seed = int(sample_seed)
            if sample_seed < 0:
                raise ValueError(
                    f"sample_seed must be >= 0, got {sample_seed}"
                )
        if adapter is not None:
            adapter = str(adapter)
            if self.adapters is None:
                raise ValueError(
                    f"request names adapter {adapter!r} but this engine "
                    f"has no adapter pool — build it with "
                    f"ServeConfig(max_adapters=N, adapter_rank=r)"
                )
        if len(prompt) + max_new_tokens > self.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_model_len "
                f"({self.max_model_len})"
            )
        if len(prompt) > self.max_prompt_len and self._chunk is None:
            raise ValueError(
                f"prompt ({len(prompt)}) exceeds the largest prefill "
                f"bucket ({self.max_prompt_len}); raise max_model_len "
                f"to a multiple of block_size, pass prefill_buckets, "
                f"or enable chunked prefill (ServeConfig.prefill_chunk)"
            )
        if any(not 0 <= t < self.family.vocab_size for t in prompt):
            raise ValueError("prompt token outside the vocab")
        if self._error is not None:
            raise RuntimeError(
                "serve engine is dead (its loop raised; see the chained "
                "error) — build a fresh ServeEngine"
            ) from self._error
        rid = rid or uuid.uuid4().hex[:12]
        trace_ctx, trace_local = _trace_ctx, False
        if trace_ctx is None and self.tracer.enabled:
            # No upstream context (in-process submission on a tracing
            # engine): this engine owns the trace root.
            from ray_lightning_tpu.telemetry.propagate import root_context

            trace_ctx, trace_local = root_context(rid), True
        req = Request(
            rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=float(temperature), eos_token_id=eos_token_id,
            top_k=top_k, spec=spec, adapter=adapter,
            deadline_s=deadline_s, sample_seed=sample_seed,
            on_token=on_token, trace=trace_ctx, recv_t=_recv_t,
        )
        req._trace_local = trace_local
        if _handoff is not None:
            self._refuse_block_transfer("import_blocks")
            req._handoff = _handoff
        handle = ServeHandle(rid, req)
        with self._lock:
            if adapter is not None:
                # Resolved under the SAME lock that enqueues: a
                # remove_adapter/add_adapter on another thread either
                # completes first (unknown name -> the typed rejection
                # below) or sees this request via references_adapter —
                # a slot can never be re-issued to a new tenant while a
                # request resolved against the old one is in flight.
                try:
                    req._adapter_slot = self.adapters.slot_of(adapter)
                except KeyError:
                    raise ValueError(
                        f"unknown adapter {adapter!r} — hot-load it "
                        f"first (engine.add_adapter / "
                        f"serve_adapter_load frame)"
                    ) from None
            self.stats.bump("submitted")
            accepted = self.scheduler.submit(req)
            if accepted:
                self._handles[rid] = handle
            else:
                self._done_feed.append((rid, "rejected"))
        if not accepted:
            self.stats.bump("rejected")
            req.finished_t = time.monotonic()
            handle._done.set()
        return handle

    def generate(self, prompt: Sequence[int], max_new_tokens: int,
                 timeout: Optional[float] = 60.0, **kw) -> List[int]:
        """Blocking convenience: submit + drive (when no background
        thread runs) + result."""
        handle = self.submit(prompt, max_new_tokens, **kw)
        if self._thread is None:
            self.run_until_idle()
        return handle.result(timeout)

    # -- the loop ------------------------------------------------------------
    def step(self) -> bool:
        """One serve iteration: drain the queue plane, expire/admit,
        grow/preempt, one decode (or draft→verify) tick.  Returns True
        when any work was done (False = idle).

        The iteration is cut into the ``PHASES["serve"]`` phases
        (``telemetry/spans.py``), chained on single clock reads so they
        tile it, and with ``between`` the time since the turn before:
        each is a profiler annotation ``rlt:serve/<phase>`` and a
        counter ``tick_<phase>_us``, summed locally and handed to the
        stats under one lock at the end."""
        ph, t0 = self._open_turn("inbox")
        try:
            return self._step(ph)
        finally:
            self._close_turn(ph, t0, ticks=1)

    def _tick_phase(self, name: str):
        """A phase of the loop's own (``PHASES["serve"]``).  It is a
        span too only while a request is in the engine: idle, the loop
        turns every ``idle_wait_s`` and five spans a turn would push
        the requests' spans out of the ring."""
        inbox = self._inbox
        busy = self.scheduler.has_work() or (
            inbox is not None and not inbox.empty())
        return (self.tracer.phase if busy else phase)(
            name, "serve", self._tick_us, "tick_")

    def _open_turn(self, name: str):
        """Open a turn of the loop (an iteration, or an idle sleep) in
        its first phase ``name``, on the clock read that closes the
        ``between`` since the turn before, which this turn counts as its
        own.  Returns the entered phase and the turn's start."""
        if self._between is not None \
                and self._turn_thread != threading.get_ident():
            self._drop_between()    # another thread's turn: no chain
        prev, self._between = self._between, None
        ph = self._tick_phase(name)
        if prev is not None:
            return ph.after(prev), prev.t0
        if self._watch is not None:
            self._watch.rebase()
        ph.__enter__()
        return ph, ph.t0

    def _close_turn(self, ph, t0: float, ticks: int) -> None:
        """Close a turn on the clock read that opens ``between``, and
        hand its phases to the stats under one lock, with what its
        thread was doing and, where it stalled, its record."""
        tick, self._tick_us = self._tick_us, {}
        self._between = self._tick_phase("between").after(ph)
        self._turn_thread = threading.get_ident()
        tick["tick_us"] = wall_us = round((self._between.t0 - t0) * 1e6)
        tick["ticks"] = ticks
        stall = None
        if self._watch is not None:
            compiles = compile_event_count()
            counts = self._turn_counts
            stall = self._watch.turn(
                tick, wall_us, compiles != self._compiles_seen,
                self._turn_slots, self._turn_buckets,
                "decode_ahead" in counts, "decode_fed_on_device" in counts)
            self._compiles_seen = compiles
            self._turn_slots, self._turn_buckets = 0, ()
            self._turn_counts = {}
        self.stats.bump_many(tick, stall)
        if stall is not None:
            # Where a trace holds the stall it shows where it ended; at
            # tier ``full`` the record is a span in the requests' ring.
            with phase("stall", "serve", wall_us=wall_us,
                       phase=stall["phase"], verdict=stall["verdict"]):
                pass
            self.tracer.record("stall", stall["t_ns"] / 1e9, wall_us / 1e6,
                               args=stall)

    def _drop_between(self) -> None:
        """Leave the loop: the ``between`` open since the last turn is
        closed and not counted (the next turn starts its own clock)."""
        prev, self._between = self._between, None
        if prev is not None:
            prev.__exit__(None, None, None)
            self._tick_us = {}

    def _step(self, ph) -> bool:
        import jax.numpy as jnp

        _fault_fire("replica_tick")
        self._drain_inbox()
        ph.then("schedule")
        with self._lock:
            if self.prefix_cache is not None and self._prefix_drops:
                # Invalidate replaced/removed tenants' chains BEFORE
                # admitting: adapter-keyed KV must never be claimed
                # against different factors than wrote it.
                for name in self._prefix_drops:
                    self.prefix_cache.drop(name)
                self._prefix_drops.clear()
            admissions, expired = self.scheduler.poll()
        worked = bool(admissions) or bool(expired)
        if admissions:
            self._turn_buckets = [bucket for _, _, bucket in admissions]
        for req in expired:
            self.stats.bump("expired")
            self._finish_handle(req)
        now = time.monotonic()
        t_adm = now
        tr = self.tracer
        for slot, req, bucket in admissions:
            ph.then("admit_dispatch", rid=req.rid, bucket=bucket,
                    prompt_len=req.prompt_len,
                    full_blocks=len(self.scheduler._blocks[slot]),
                    window_blocks=len(self.scheduler._window[slot]))
            wait = now - req.arrival_t
            self.stats.note_admitted(
                wait, None if req.recv_t is None else now - req.recv_t)
            ctx = req.trace if tr.enabled else None
            if ctx is not None:
                tr.record(
                    "queue_wait", time.time() - wait, wait,
                    args=trace_args(child_context(ctx), rid=req.rid,
                                    preemptions=req.preemptions),
                )
                self.stats.note_phase("queue_wait", wait)
            if bucket == 0:
                # Prefix-claimed and/or chunked admission (exact block
                # coverage, no bucket padding): the uncovered suffix
                # runs through the fixed-width chunk program — inline
                # when it fits one dispatch, one chunk per step
                # (interleaved with decode ticks) otherwise.
                suffix_len = req.prompt_len - req.claimed_tokens
                if self._chunk is not None and suffix_len > self._chunk:
                    self._start_chunk_job(slot, req)
                    continue
                handoff = None
                self.stats.bump("prefills")
                rph = self._request_phase(ctx, "prefill_compute", req,
                                          bucket=bucket)
                first = self._suffix_prefill(slot, req)
            else:
                ids = np.asarray(  # rlt: noqa[RLT002] host block list, no device value
                    self.scheduler._blocks[slot][: bucket
                                                 // self.config.block_size],
                    np.int32,
                )
                ids = jnp.asarray(ids)
                if self.family.two_kind:
                    # (full blocks of the bucket, the slot's window ring)
                    ids = (ids, jnp.asarray(
                        self.scheduler.window_tables[slot]))
                handoff = getattr(req, "_handoff", None)
                padded = None
                if handoff is None or self.draft_module is not None:
                    # The padded prompt feeds the local prefill and/or
                    # the draft prefill; a KV import on a draft-less
                    # engine — the disaggregated steady state — needs
                    # neither, so skip the bucket-sized host→device
                    # copy entirely.
                    padded_np = np.zeros((bucket,), np.int32)
                    padded_np[: req.prompt_len] = req.prompt
                    padded = jnp.asarray(padded_np)
                rph = self._request_phase(
                    ctx, "decode_admission" if handoff is not None
                    else "prefill_compute", req, bucket=bucket)
            if bucket != 0 and handoff is not None:
                # A prefill worker already ran this prompt: scatter its
                # exported blocks into OUR allocator's blocks and
                # sample the first token from the shipped logits —
                # bitwise what the local prefill would have produced,
                # without the trunk forward.
                req._handoff = None  # the payload is large; drop it
                self.stats.bump("kv_imports")
                self._pool = self._import_fn(
                    self._pool,
                    {k: jnp.asarray(v) for k, v in handoff["kv"].items()},
                    ids,
                )
                first = self._first_fn(
                    jnp.asarray(handoff["logits"]),
                    np.int32(req.prompt_len),
                    np.float32(req.temperature),
                    np.int32(req.sample_seed), np.int32(req.top_k or 0),
                )
            elif bucket != 0:
                self.stats.bump_many({
                    "prefills": 1, "prefill_bucket_positions": bucket,
                    "prefill_prompt_positions": req.prompt_len})
                ad = None if self.adapters is None \
                    else self.adapters.buffers
                ad_id = None if self.adapters is None \
                    else np.int32(req._adapter_slot)
                first, self._pool, *_ = self._prefill_fn(
                    self.params, self._pool, padded,
                    np.int32(req.prompt_len), ids,
                    np.float32(req.temperature),
                    np.int32(req.sample_seed),
                    np.int32(req.top_k or 0),
                    ad, ad_id,
                )
            if bucket != 0 and self.draft_module is not None:
                # The draft cache tracks every admission (one bucketed
                # draft-prefill program per bucket) so any later tick
                # can speculate for this slot.
                self._draft_pool = self._draft_prefill_fn(
                    self.draft_params, self._draft_pool, padded,
                    np.int32(req.prompt_len), ids,
                )
            # The first token stays on the device.  Where the loop runs
            # a tick ahead and needs no token's value to go on (no
            # ``eos_token_id``, a bucketed admission), the next decode
            # is fed it there and dispatched before the host blocks on
            # it (``_decode_tick``); otherwise the sync sits here, as it
            # always did, and the decode follows it.
            self._firsts.append(
                (slot, req, first, rph, time.monotonic() - t_adm))
            if not (self._pipelined and bucket != 0
                    and req.eos_token_id is None):
                self._fetch_firsts(ph)
            t_adm = time.monotonic()

        # One chunk for every in-flight chunked prefill BEFORE the
        # decode tick: both dispatches queue on the device each step,
        # so resident slots keep emitting one token per step while a
        # long prompt fills in chunk by chunk (the no-stall contract).
        if self._chunk_jobs:
            ph.then("chunk")
            worked = self._chunk_tick() or worked

        ph.then("grow")
        # Per-slot speculative widths for THIS tick: the engine K,
        # capped per request (spec= knob) and by the tokens it has left
        # (a tick never drafts past max_new_tokens).  Zero everywhere
        # when no draft model is loaded.
        widths = self._tick_widths()

        # Growth (and preemption when the pool is dry) for every slot
        # about to write past its allocated blocks.  Preemption is only
        # ever for BASELINE coverage — the one position a plain decode
        # write needs (round-11 semantics, unchanged).  The speculative
        # window is claimed OPPORTUNISTICALLY on top: if the pool can't
        # cover seq_len + width, the slot drafts fewer tokens this tick
        # (down to zero) rather than evicting a neighbour — speculation
        # is a throughput bet, and a bet must never cost another
        # request its progress (two spec slots preempting each other's
        # windows would ping-pong without forward progress).
        # A slot whose request ends with the first token it still waits
        # for (by count) is never decoded for, nor grown.
        skip = set(self._chunk_jobs).union(
            slot for slot, req, *_ in self._firsts
            if req.max_new_tokens <= 1)
        active = [
            s for s, r in enumerate(self.scheduler.slots)
            if r is not None and s not in skip
        ]
        for slot in list(active):
            if self.scheduler.slots[slot] is None:
                continue  # preempted by an earlier slot's growth
            while self.scheduler.needs_block(slot):
                if self.scheduler.grow(slot):
                    break
                victim = self.scheduler.preempt_youngest(protect=slot)
                if victim is None:
                    # Only this request is live and the pool is dry —
                    # impossible under the init-time sizing invariant.
                    raise RuntimeError(
                        "block pool exhausted with a single live "
                        "request — num_blocks below one sequence"
                    )
                self.stats.bump("preempted")
        for slot, req in enumerate(self.scheduler.slots):
            if req is None or widths[slot] == 0:
                continue
            w = widths[slot]
            # rlt: noqa[RLT002] host np state
            seq_len = int(self.scheduler.seq_lens[slot])
            while w > 0 and not self.scheduler.cover(slot, seq_len + w):
                w -= 1  # pool can't fund the window: draft less
            widths[slot] = w

        active = [
            s for s, r in enumerate(self.scheduler.slots)
            if r is not None and s not in skip
        ]
        if active:
            worked = True
            self._turn_slots = len(active)
            ph.then("decode_dispatch", slots=len(active))
            if any(widths[s] > 0 for s in active):
                self._spec_tick(active, widths, ph)
            else:
                self._decode_tick(active, ph)
        else:
            self._ahead = None  # every slot it computed for is gone
            self._fetch_firsts(ph)  # no decode to dispatch before them
        ph.then("housekeep")
        self._refresh_gauges()
        self._maybe_export()
        return worked

    def _request_phase(self, ctx, name: str, req, **args):
        """An entered span of ONE traced request (``PHASES["request"]``)
        through the same primitive as the tick's phases, or None when
        the request carries no trace context."""
        if ctx is None:
            return None
        return self.tracer.phase(
            name, "request",
            **trace_args(child_context(ctx), rid=req.rid, **args),
        ).__enter__()

    def _fetch_firsts(self, ph) -> None:
        """Fetch and emit the first token of every admission that still
        waits for it, in the order of their prefills (phases
        ``admit_wait``, then ``admit_emit``, an admission).  A slot whose
        request left since its prefill was dispatched (cancelled,
        preempted) is skipped, as a slot that leaves while a decode is in
        flight is."""
        firsts, self._firsts = self._firsts, []
        sched = self.scheduler
        for slot, req, first, rph, spent in firsts:
            if sched.slots[slot] is not req:
                if rph is not None:
                    rph.__exit__(None, None, None)
                continue
            ph.then("admit_wait", rid=req.rid)
            t_wait = time.monotonic()
            # The TTFT sync.  It sits at the admission where the decode
            # needs the token's value first (the serial loop, a request
            # with an eos); else behind the dispatch of the decode this
            # token feeds, so that the device's queue is not empty while
            # the host waits here.
            # rlt: noqa[RLT002] deliberate TTFT sync, placed as said above
            first = int(first)
            ph.then("admit_emit", rid=req.rid)
            t_first = time.monotonic()
            # Per-admission wall in µs (host prep + prefill/import
            # dispatch + the TTFT sync above).  Paired with the
            # `admitted` counter it gives the capacity oracle the
            # once-per-request admission cost its saturation model
            # charges (serve/capacity.py).
            self.stats.bump(  # rlt: noqa[RLT002] host float, no device value
                "admit_us", int((spent + t_first - t_wait) * 1e6))
            # A decode dispatched behind this prefill starts on the
            # device no earlier than now.
            self._fetched_t = t_first
            if rph is not None:
                # The int() above synced the device, so this interval
                # covers dispatch + device compute of the admission.
                rph.__exit__(None, None, None)
                self.stats.note_phase(rph.name, rph.dur)
                rph = self._request_phase(req.trace, "first_token", req,
                                          token_index=0)
            self.stats.note_first_token(t_first - req.arrival_t)
            done = sched.append_token(slot, first, now=t_first)
            if rph is not None:
                rph.__exit__(None, None, None)
                self.stats.note_phase("first_token", rph.dur)
            self.stats.bump("tokens_out")
            if req.adapter is not None:
                self.stats.note_adapter(req.adapter, tokens=1)
            self._cur_tokens[slot] = first
            if self.prefix_cache is not None:
                self._prefix_insert(slot, req)
            if done:
                self._complete(slot)

    def _tick_widths(self) -> List[int]:
        """Drafted tokens per slot this tick (0 = plain decode)."""
        widths = [0] * self.config.num_slots
        if self.spec_k == 0:
            return widths
        for slot, req in enumerate(self.scheduler.slots):
            if req is None or slot in self._chunk_jobs:
                continue
            k = self.spec_k if req.spec is None else min(
                req.spec, self.spec_k
            )
            remaining = req.max_new_tokens - len(req.generated)
            widths[slot] = max(0, min(k, remaining - 1))
        return widths

    def _lora_operands(self):
        """``(stacked adapter buffers, per-slot adapter_ids operand)``
        for this tick — ``(None, None)`` on pool-less engines, which
        keeps their compiled graphs byte-identical to pre-LoRA rounds.
        The buffers reference is read once per tick: a concurrent hot
        add swaps the pool's (immutable) tree atomically, and a new
        slot cannot appear in ``adapter_slots`` before its add()
        returned — so a tick sees either the old world or the new one,
        never a torn mix."""
        import jax.numpy as jnp

        if self.adapters is None:
            return None, None
        # (a copy: the upload may alias the host's memory, and a slot's
        # release rewrites it while a decode may still be queued)
        return self.adapters.buffers, jnp.asarray(
            self.scheduler.adapter_slots.copy()
        )

    def _tick_top_ks(self):
        """``top_ks`` operand for this tick, or None when NO slot uses
        top-k — the None variant compiles without the full-vocab sort,
        so greedy/temperature-only traffic (the common mix) never pays
        sorted-vocab work per dispatch.  The sorted variant compiles
        once on the first top-k tick, like a fresh prefill bucket."""
        import jax.numpy as jnp

        if not np.any(self.scheduler.top_ks > 0):
            return None
        return jnp.asarray(self.scheduler.top_ks.copy())  # as above

    # -- prefix cache + chunked prefill -------------------------------------
    def _claim_prefix(self, req) -> List[int]:
        """Scheduler claim hook: refcount-claim the resident blocks
        covering the longest whole-block shared prefix of ``req``'s
        prompt.  The cap ``(prompt_len - 1) // Bs`` keeps the FINAL
        prompt token's block always computed locally — its forward
        produces the first-token logits, and every later write (decode,
        verify window, chunk) lands strictly PAST the claimed frontier,
        which is why claimed blocks never need copy-on-write in nominal
        serving (``Scheduler.cow_slot`` stays a defensive escape
        hatch).  Handoff admissions never claim: the wire payload
        covers the whole prompt and must scatter into private blocks."""
        if getattr(req, "_handoff", None) is not None:
            return []
        cap = (req.prompt_len - 1) // self.config.block_size
        return self.prefix_cache.claim(req.adapter, req.prompt, cap)

    def _suffix_prefill(self, slot: int, req) -> Any:
        """Prefill the uncovered suffix of a claimed (or
        short-chunkable) admission in ONE chunk-program dispatch and
        return the (device) first token.  The window width is the
        smallest prefill bucket covering the suffix — re-using the
        bucketed shape set — or the fixed chunk width for suffixes past
        the largest bucket, so the executable set stays bounded."""
        import jax.numpy as jnp

        sched = self.scheduler
        start = req.claimed_tokens
        suffix = req.prompt_len - start
        width = next(
            (b for b in sched.buckets if b >= suffix), self._chunk
        )
        window = np.zeros((1, width), np.int32)
        window[0, :suffix] = req.prompt[start:]
        table_row = jnp.asarray(sched.block_tables[slot: slot + 1])
        start_arr = jnp.asarray(np.full((1,), start, np.int32))
        limit = jnp.asarray(np.full((1,), req.prompt_len, np.int32))
        tokens = jnp.asarray(window)
        ad = None if self.adapters is None else self.adapters.buffers
        ad_ids = None if self.adapters is None else jnp.asarray(
            [req._adapter_slot], jnp.int32
        )
        tok, self._pool = self._chunk_fn(
            self.params, self._pool, table_row, start_arr, tokens,
            limit, np.int32(suffix - 1), np.float32(req.temperature),
            np.int32(req.sample_seed), np.int32(req.top_k or 0),
            ad, ad_ids,
        )
        if self.draft_module is not None:
            self._draft_pool = self._draft_chunk_fn(
                self.draft_params, self._draft_pool, table_row,
                start_arr, tokens, limit,
            )
        self.stats.bump_many({
            "prefill_chunks": 1, "prefill_bucket_positions": width,
            "prefill_prompt_positions": suffix})
        return tok

    def _start_chunk_job(self, slot: int, req) -> None:
        """Begin a chunked prefill: park the slot OUT of the decode set
        (scheduler row trashed, seq_len 0 — the compiled decode program
        treats it exactly like an inactive slot) and remember its real
        block-table row privately.  One chunk advances per engine step,
        interleaved with decode ticks, so resident decode slots keep
        emitting while a 32k prompt fills in."""
        from ray_lightning_tpu.serve.kv_cache import TRASH_BLOCK

        sched = self.scheduler
        row = sched.block_tables[slot].copy()
        sched.block_tables[slot, :] = TRASH_BLOCK
        sched.seq_lens[slot] = 0
        sched.draft_lens[slot] = 0
        self.stats.bump("prefills")
        self._chunk_jobs[slot] = _PrefillJob(
            req=req, row=row, next_pos=req.claimed_tokens
        )

    def _chunk_tick(self) -> bool:
        """Advance every in-flight chunked prefill by exactly ONE chunk
        (the no-stall contract: a long prompt costs resident decode
        slots one chunk dispatch per step, never the whole prefill).
        The final chunk samples the first token (bitwise the tail of
        the bucketed prefill), restores the slot's scheduler row, and
        hands the request to the ordinary decode path."""
        import jax.numpy as jnp

        if not self._chunk_jobs:
            return False
        sched = self.scheduler
        worked = False
        for slot, job in list(self._chunk_jobs.items()):
            if sched.slots[slot] is not job.req:
                # The request was preempted (or force-finished) out
                # from under the job: its blocks are already freed and
                # a requeued re-admission restarts cleanly, so the
                # stale job is simply dropped.
                del self._chunk_jobs[slot]
                continue
            req = job.req
            start = job.next_pos
            width = self._chunk
            end = min(start + width, req.prompt_len)
            last = end == req.prompt_len
            window = np.zeros((1, width), np.int32)
            window[0, : end - start] = req.prompt[start:end]
            table_row = jnp.asarray(job.row[None, :])
            start_arr = jnp.asarray(np.full((1,), start, np.int32))
            limit = jnp.asarray(np.full((1,), end, np.int32))
            sample_idx = np.int32(
                (req.prompt_len - 1 - start) if last else 0
            )
            tokens = jnp.asarray(window)
            ad = None if self.adapters is None else self.adapters.buffers
            ad_ids = None if self.adapters is None else jnp.asarray(
                [req._adapter_slot], jnp.int32
            )
            tok, self._pool = self._chunk_fn(
                self.params, self._pool, table_row, start_arr, tokens,
                limit, sample_idx, np.float32(req.temperature),
                np.int32(req.sample_seed), np.int32(req.top_k or 0),
                ad, ad_ids,
            )
            if self.draft_module is not None:
                self._draft_pool = self._draft_chunk_fn(
                    self.draft_params, self._draft_pool, table_row,
                    start_arr, tokens, limit,
                )
            self.stats.bump_many({
                "prefill_chunks": 1, "prefill_bucket_positions": width,
                "prefill_prompt_positions": end - start})
            job.next_pos = end
            worked = True
            if not last:
                continue
            # Final chunk landed: the private row goes live and the
            # slot joins the fixed-width decode set next tick.
            del self._chunk_jobs[slot]
            first = int(tok)  # rlt: noqa[RLT002] deliberate TTFT sync at admission
            sched.block_tables[slot, :] = job.row
            sched.seq_lens[slot] = req.prompt_len
            sched.draft_lens[slot] = req.prompt_len
            t_first = time.monotonic()
            self.stats.note_first_token(t_first - req.arrival_t)
            done = sched.append_token(slot, first, now=t_first)
            self.stats.bump("tokens_out")
            if req.adapter is not None:
                self.stats.note_adapter(req.adapter, tokens=1)
            self._cur_tokens[slot] = first
            if self.prefix_cache is not None:
                self._prefix_insert(slot, req)
            if done:
                self._complete(slot)
        return worked

    def _prefix_insert(self, slot: int, req) -> None:
        """Publish the slot's whole-block prompt prefix into the
        cache.  Claimed blocks just re-match during the walk (nothing
        re-stored); freshly computed full blocks are retained by the
        index, so they survive the request's release and the NEXT
        prompt sharing them claims instead of recomputing."""
        n = req.prompt_len // self.config.block_size
        if n == 0:
            return
        self.prefix_cache.insert(
            req.adapter, req.prompt, self.scheduler._blocks[slot][:n]
        )

    def _dispatch_decode(self, active: List[int], tokens=None) -> tuple:
        """Dispatch one decode for the slots ``active`` and return what
        the tick that reads it needs: dispatch time, the (slot, request)
        pairs it computes for, the tokens and the family's sums (still
        on the device), and the tick's block counters.  ``tokens`` is
        the last decode's output where it lies on the device, for a tick
        dispatched before that output is fetched; None takes the host's
        ``_cur_tokens``.  Either way the first token of an admission
        among ``active`` that is not fetched yet (``_firsts``) is written
        at its slot on the device (``_feed_fn``)."""
        import jax.numpy as jnp

        from ray_lightning_tpu.serve.kv_cache import TRASH_BLOCK

        t0 = time.monotonic()
        sched = self.scheduler
        # A slot that holds a request and is not among ``active`` ends
        # with the tick in hand (or is mid chunked prefill, and reads so
        # already): this decode computes it as the empty slot it is about
        # to be (length 0, every block the trash block).
        off = None
        if len(active) < sched.active_slots:
            off = np.ones((self.config.num_slots,), bool)
            off[active] = False

        def up(host, empty=None):
            # A copy: the host goes on to change these arrays while the
            # decode that reads them may still be queued on the device,
            # and an upload may alias the host's memory (on the CPU it
            # does).
            host = host.copy()
            if off is not None and empty is not None:
                host[off] = empty
            return jnp.asarray(host)

        seq_lens = up(sched.seq_lens, 0)
        cur = up(self._cur_tokens) if tokens is None else tokens
        fed = 0
        for slot, req, first, *_ in self._firsts:
            if slot in active and sched.slots[slot] is req:
                cur = self._feed_fn(cur, np.int32(slot), first)
                fed += 1
        tables = up(sched.block_tables, TRASH_BLOCK)
        if self.family.two_kind:
            tables = (tables, up(sched.window_tables, TRASH_BLOCK))
        ad, ad_ids = self._lora_operands()
        toks, self._pool, *sums = self._decode_fn(
            self.params, self._pool, tables, seq_lens, cur,
            up(sched.temperatures), up(sched.sample_seeds),
            self._tick_top_ks(), ad, ad_ids,
        )
        if self.draft_module is not None:
            # Mirror the write into the draft cache so its frontier
            # claim below stays TRUE: a fallback tick on a speculative
            # engine (pool pressure shrank every window to zero) must
            # not leave a silent gap that degrades every later draft
            # proposal for the sequence.
            _, self._draft_pool = self._draft_step_fn(
                self.draft_params, self._draft_pool, tables, seq_lens,
                cur, cur, jnp.ones((self.config.num_slots,), bool),
                seq_lens + 1,
            )
            self.stats.bump("draft_steps")
        # What the paged decode kernel has to read against what the
        # slots' tables span: host integers, before the lengths advance.
        # A slot at seq_len holds positions [0, seq_len] in
        # seq_len // Bs + 1 blocks, the one being written included.
        resident = self.scheduler.seq_lens[active] // self.config.block_size
        # rlt: noqa[RLT002] host ints (scheduler.seq_lens), no device value
        read = int(resident.sum()) + len(active)
        kv_blocks = {"read": read, "table": self.scheduler.block_tables.size}
        if self.family.two_kind:
            # Per layer, by kind of state: a full layer reads the
            # resident blocks of its table, a sliding layer the slot's
            # whole ring; the unsuffixed counters stay the sums.
            ring = self.scheduler.window_tables
            n_full, n_ring = self.family.n_full, self.family.n_ring
            by_kind = {
                "read_full": read * n_full,
                "table_full": kv_blocks["table"] * n_full,
                "read_window": len(active) * ring.shape[1] * n_ring,
                "table_window": ring.size * n_ring,
            }
            kv_blocks = {
                "read": by_kind["read_full"] + by_kind["read_window"],
                "table": by_kind["table_full"] + by_kind["table_window"],
                **by_kind,
            }
        kv_blocks = {f"decode_kv_blocks_{k}": v for k, v in kv_blocks.items()}
        if self.family.two_kind:
            # Positions the full layers' decode attends this tick, the
            # current token's own included, over the full layers.
            # rlt: noqa[RLT002] host ints (scheduler.seq_lens), no device value
            kv_blocks["decode_full_positions"] = self.family.n_full * int(
                self.scheduler.seq_lens[active].sum() + len(active))
        if self._ring_layers:
            # Positions the ring kernel attends this tick, the current
            # token's own included: the window's, at most, a slot.
            # rlt: noqa[RLT002] host ints (scheduler.seq_lens), no device value
            kv_blocks["decode_ring_positions"] = self._ring_layers * int(
                np.minimum(self.scheduler.seq_lens[active],
                           self.family.cfg.window - 1).sum() + len(active))
        if self._n_latent:
            # Positions the latent decode attends this tick, the current
            # token's own included, over the latent layers.
            # rlt: noqa[RLT002] host ints (scheduler.seq_lens), no device value
            kv_blocks["decode_latent_positions"] = self._n_latent * int(
                self.scheduler.seq_lens[active].sum() + len(active))
        if self._walk_layers:
            # What the walk kernels compute this tick, over the layers
            # that run one (none where the decode takes the XLA gather):
            # online-softmax steps, and positions, the trimmed tails'
            # masked ones included.
            steps, positions = walk_plan(
                self.scheduler.seq_lens[active],
                self.scheduler.block_tables.shape[1], self.config.block_size)
            kv_blocks["decode_attn_steps"] = self._walk_layers * steps
            kv_blocks["decode_attn_positions_computed"] = (
                self._walk_layers * positions)
        if sums:
            kv_blocks["moe_tokens_routed"] = len(active) * self.family.n_sparse
        if fed:
            kv_blocks["admit_fed_on_device"] = fed
        held = [(slot, self.scheduler.slots[slot]) for slot in active]
        return t0, held, toks, sums, kv_blocks

    def _decode_tick(self, active: List[int], ph) -> None:
        """One token for every active slot — the non-speculative path
        (and the fallback when no active slot drafts this tick).
        ``ph`` is the tick's open phase (``decode_dispatch``).

        A pipelined engine (``_pipelined``) runs one tick ahead of the
        host where the tick's slots allow it: the tick may find its
        decode already dispatched (by the tick before, which then covers
        the slots of THAT moment: a slot cancelled, expired or preempted
        since is skipped), and dispatches the next one on this one's
        tokens where they lie on the device, before it fetches them;
        where it cannot know without the tokens that every slot goes on
        (an ``eos_token_id``), after the fetch and before the replies.

        An admission joins that pipeline.  Its prefill was dispatched by
        ``_step`` and its first token is still on the device
        (``_firsts``): the next decode is dispatched for the admitted
        slot too, fed that token there, and only then does the host
        block, in the device's order: on the tokens of the decode in hand
        (replies out), then on each first token (``_fetch_firsts``).
        Where no decode was in hand the prefills come first on the
        device, and so do their fetches.  So across an admission the
        device's queue holds the decode behind the prefill before the
        host waits for either."""
        sched = self.scheduler
        ahead, self._ahead = self._ahead, None
        if ahead is not None:
            live = [slot for slot, req in ahead[1]
                    if sched.slots[slot] is req]
            if live:
                active = live
            else:
                ahead = None    # every slot it computed for is gone
        t0, _, toks, sums, counts = ahead or self._dispatch_decode(active)
        self._turn_counts = counts
        if ahead is None:
            # This decode was fed the first tokens: they are its slots'.
            self._fetch_firsts(ph)
        # The lengths advance without the tokens: the host knows them.
        for slot in active:
            sched.seq_lens[slot] += 1
            sched.draft_lens[slot] = sched.seq_lens[slot]
        going = self._going_on(active, fetched=False)
        if going:
            ph.then("decode_dispatch", slots=len(going), ahead=1)
            self._ahead = self._dispatch_decode(going, tokens=toks)
            # counted when read
            self._ahead[-1].update(decode_ahead=1, decode_fed_on_device=1)
        ph.then("decode_wait")
        # rlt: noqa[RLT002] deliberate: the tick must emit tokens
        toks = np.asarray(toks)
        ph.then("emit")
        # A decode dispatched behind another queued on the device until
        # that one's tokens were out: its cost counts from there.
        now = time.monotonic()
        dt = now - max(t0, self._fetched_t)
        self._fetched_t = now
        self.stats.bump("decode_steps")
        if sums:
            # rlt: noqa[RLT002] the same program's output as the tokens above
            local, hit = (int(v) for v in np.asarray(sums[0]))
            counts.update(moe_local_assignments=local,
                          moe_local_experts_hit=hit)
        self.stats.bump_many(counts)  # rlt: noqa[RLT002] host ints, no device value
        # Tick wall in µs — with decode_steps/tokens_out it gives the
        # capacity oracle per-bin (busy slots, tick cost) pairs, the
        # data its affine tick-cost fit needs (serve/capacity.py).
        self.stats.bump(  # rlt: noqa[RLT002] host float, no device value
            "decode_us", int(dt * 1e6))
        self.stats.note_token_latency(dt, n_tokens=len(active))
        for slot in active:
            # rlt: noqa[RLT002] host np after the tick fetch
            self._cur_tokens[slot] = int(toks[slot])
        if not going:
            going = self._going_on(active, fetched=True)
            if going:
                ph.then("decode_dispatch", slots=len(going), ahead=1)
                self._ahead = self._dispatch_decode(going)
                self._ahead[-1]["decode_ahead"] = 1     # counted when read
                ph.then("emit")
        with self._coalesced_replies():
            for slot in active:
                tok = int(self._cur_tokens[slot])  # rlt: noqa[RLT002] host np
                req = sched.slots[slot]
                if req is not None and req.adapter is not None:
                    self.stats.note_adapter(req.adapter, tokens=1)
                done = sched.append_token(slot, tok)
                if done:
                    self._complete(slot)
        self._fetch_firsts(ph)

    def _going_on(self, active: List[int], fetched: bool) -> List[int]:
        """The slots the next decode can be dispatched for now, before
        this tick's tokens (lengths advanced already) are booked: those
        that go on after their token, if nothing waits for a slot it
        could have (while no admission's prefill is on the device) and
        every next position has its block; else none.
        Before the tokens are ``fetched`` into ``_cur_tokens`` an
        ``eos_token_id`` cannot be tested: that holds the tick back until
        they are.  A slot that is not among ``active`` was admitted since
        the decode in hand was dispatched.  It goes on with the others
        when its first token still lies on the device (``_firsts``: the
        decode is fed it there) and is not its last by count; a token
        that is on the host only (an admission that synced) holds the
        tick back until the fetch, like an eos."""
        sched = self.scheduler
        if not self._pipelined or not active:
            return []
        ticked, going = set(active), []
        on_device = {slot: req for slot, req, *_ in self._firsts}
        for slot, req in enumerate(sched.slots):
            if req is None:
                continue
            if slot in ticked:
                if len(req.generated) + 1 >= req.max_new_tokens:
                    continue    # ends with this tick's token, by count
                if req.eos_token_id is not None:
                    if not fetched:
                        return []
                    if int(self._cur_tokens[slot]) == req.eos_token_id:
                        continue    # ends with this tick's token, at eos
            elif on_device.get(slot) is req:
                if req.max_new_tokens <= 1:
                    continue    # ends with its first token, by count
            elif not fetched:
                return []
            going.append(slot)
        if not going:
            return []
        if len(going) < len(sched.slots) and not on_device and (
                sched.queue or (
                    self._inbox is not None and not self._inbox.empty())):
            # A slot is, or falls, free for what waits: the next
            # iteration admits it into the very next decode.  Not while
            # a prefill of this iteration is on the device: the decode
            # goes behind it now, and what waits joins a tick later,
            # rather than leave the device idle behind that prefill.
            return []
        for slot in going:
            if sched.needs_block(slot) and not sched.grow(slot):
                return []   # pool dry: the loop's grow phase preempts
        return going

    def _spec_tick(self, active: List[int], widths: List[int],
                   ph) -> None:
        """One draft-propose / target-verify round.

        1. the draft model proposes up to K tokens per slot — K+1
           dispatches of its fixed-width decode program chained on
           device (the first dispatch doubles as the catch-up write for
           slots whose draft cache trails by the bonus token);
        2. the target scores every slot's (current token + drafts)
           window in ONE K+1-wide verify dispatch, sampling its own
           token at each position with the request's position-keyed
           streams;
        3. host-side accept/reject keeps each slot's longest agreeing
           draft prefix plus the target's token at the first
           disagreement (== the bonus token when everything agreed),
           emits that variable-width batch, and rolls both caches back
           to the emitted frontier (blocks past it return to the pool).

        Greedy slots emit exactly the tokens sequential greedy decode
        would: every accepted draft MATCHED the target argmax, and the
        corrected token IS the target argmax at the first mismatch.
        """
        import jax.numpy as jnp

        sched = self.scheduler
        K = self.spec_k
        t0 = time.monotonic()
        limits = np.zeros((self.config.num_slots,), np.int32)
        for slot in active:
            limits[slot] = (  # rlt: noqa[RLT002] host np state
                int(sched.seq_lens[slot]) + widths[slot] + 1
            )
        gaps = np.where(  # rlt: noqa[RLT002] host scheduler arrays
            np.asarray([r is not None for r in sched.slots]),
            sched.seq_lens - sched.draft_lens, 0,
        ).astype(np.int32)
        # Dispatch-0 token: the emitted token AT draft_lens — the
        # bonus-token catch-up write for gap-1 slots, the current token
        # (= proposal seed) for everyone else.
        start = np.zeros((self.config.num_slots,), np.int32)
        for slot in active:
            req = sched.slots[slot]
            if gaps[slot]:
                start[slot] = req.generated[  # rlt: noqa[RLT002] host np state
                    int(sched.draft_lens[slot]) - req.prompt_len
                ]
            else:
                start[slot] = self._cur_tokens[slot]
        cur = jnp.asarray(self._cur_tokens)
        limits_j = jnp.asarray(limits)
        tables = jnp.asarray(sched.block_tables)
        ones = jnp.ones((self.config.num_slots,), bool)
        outs = []
        prev = cur
        for j in range(K + 1):
            if j == 0:
                override, mask = jnp.asarray(start), ones
            elif j == 1:
                override, mask = cur, jnp.asarray(gaps > 0)
            else:
                override, mask = cur, jnp.zeros_like(ones)
            prev, self._draft_pool = self._draft_step_fn(
                self.draft_params, self._draft_pool, tables,
                jnp.asarray(sched.draft_lens + j), prev,
                override, mask, limits_j,
            )
            outs.append(prev)
        self.stats.bump("draft_steps", K + 1)
        ph.then("decode_wait")
        outs = np.stack(  # rlt: noqa[RLT002] deliberate: host accept/reject
            [np.asarray(o) for o in outs]
        )  # (K+1, W)
        ph.then("decode_dispatch")

        # Per-slot proposals: the K chain outputs starting at the
        # slot's gap offset.
        window = np.zeros((self.config.num_slots, K + 1), np.int32)
        window[:, 0] = self._cur_tokens
        for slot in active:
            g = int(gaps[slot])  # rlt: noqa[RLT002] host np state
            window[slot, 1: K + 1] = outs[g: g + K, slot]

        ad, ad_ids = self._lora_operands()
        sampled, self._pool = self._verify_fn(
            self.params, self._pool, tables,
            jnp.asarray(sched.seq_lens), jnp.asarray(window),
            limits_j, jnp.asarray(sched.temperatures),
            jnp.asarray(sched.sample_seeds), self._tick_top_ks(),
            ad, ad_ids,
        )
        ph.then("decode_wait")
        # rlt: noqa[RLT002] deliberate verify sync
        sampled = np.asarray(sampled)  # (W, K+1)
        ph.then("emit")
        self.stats.bump("verify_steps")
        dt = time.monotonic() - t0
        # Same busy-time accounting as the plain decode tick, so the
        # capacity oracle's time budget stays honest on speculative
        # engines too.
        self.stats.bump(  # rlt: noqa[RLT002] host float, no device value
            "decode_us", int(dt * 1e6))

        total_emitted = 0
        with self._coalesced_replies():
            for slot in active:
                w = widths[slot]
                drafts = window[slot, 1: w + 1]
                target = sampled[slot, : w + 1]
                accepted = 0
                while accepted < w and drafts[accepted] == target[accepted]:
                    accepted += 1
                emit = [int(t) for t in drafts[:accepted]]  # rlt: noqa[RLT002] host np
                emit.append(int(target[accepted]))  # rlt: noqa[RLT002] host np
                seq_was = int(sched.seq_lens[slot])  # rlt: noqa[RLT002] host np state
                draft_was = int(sched.draft_lens[slot])  # rlt: noqa[RLT002] host np state
                n, done = sched.append_tokens(slot, emit)
                new_len = seq_was + n
                # Roll BOTH caches back to the emitted frontier: the target
                # wrote the whole window, the draft chain wrote K+1
                # positions from its own frontier; everything past new_len
                # is rejected garbage whose blocks return to the pool.
                sched.truncate_slot_to(slot, new_len)
                sched.draft_lens[slot] = min(draft_was + K + 1, new_len)
                self._cur_tokens[slot] = emit[n - 1]
                total_emitted += n
                self.stats.note_spec_slot(w, min(accepted, n), n)
                req = sched.slots[slot]
                if req is not None and req.adapter is not None:
                    self.stats.note_adapter(req.adapter, tokens=n)
                if done:
                    self._complete(slot)
        self.stats.bump("spec_ticks")
        self.stats.note_token_latency(dt, n_tokens=total_emitted)

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        """Drive the loop synchronously until queue and slots drain."""
        try:
            for _ in range(max_steps):
                self.step()
                if not self.scheduler.has_work():
                    return
        finally:
            self._drop_between()
        raise RuntimeError(f"still busy after {max_steps} serve steps")

    def _complete(self, slot: int) -> None:
        if self.prefix_cache is not None:
            # Keep the FINISHED chain resident too — prompt plus every
            # generated token whose KV was actually written (the final
            # sampled token never was: seq_lens stops one short of it).
            # A follow-up turn that extends this conversation claims
            # the whole chain instead of re-prefilling it.
            req = self.scheduler.slots[slot]
            toks = req.prompt + req.generated[:-1]
            n = len(toks) // self.config.block_size
            if n:
                self.prefix_cache.insert(
                    req.adapter, toks, self.scheduler._blocks[slot][:n]
                )
        req = self.scheduler.finish(slot)
        e2e = req.finished_t - req.arrival_t
        self.stats.note_completed(e2e)
        if req.adapter is not None:
            self.stats.note_adapter(req.adapter, completed=1)
        if (self.tracer.enabled and req.trace is not None
                and getattr(req, "_trace_local", False)):
            # Engine-owned traces (no router upstream) anchor their own
            # root span; routed requests' roots live router-side.
            self.tracer.record(
                "request", time.time() - e2e, e2e,
                args=trace_args(req.trace, rid=req.rid,
                                status=req.state.value),
            )
        self._finish_handle(req)

    def _finish_handle(self, req) -> None:
        with self._lock:
            handle = self._handles.pop(req.rid, None)
            self._done_feed.append((req.rid, req.state.value))
        if handle is not None:
            handle._done.set()
        self._reply_done(req)

    # -- multi-tenant LoRA ---------------------------------------------------
    def add_adapter(self, name: str, adapter: dict) -> int:
        """Hot-load (or replace) one tenant's LoRA adapter; returns its
        pool slot.  Replacement of an adapter any queued/active request
        is decoding through is refused loudly — swapping factors under
        a live sequence would change its model mid-stream."""
        if self.adapters is None:
            raise ValueError(
                "engine has no adapter pool — build it with "
                "ServeConfig(max_adapters=N, adapter_rank=r)"
            )
        name = str(name)
        with self._lock:
            # Guard and load under ONE lock hold: a submit landing
            # between them would resolve the name against the factors
            # being replaced (submit resolves slots under this lock).
            if self.adapters.has(name) \
                    and self.scheduler.references_adapter(name):
                raise RuntimeError(
                    f"adapter {name!r} is serving queued/active "
                    f"requests — replacing its factors would change "
                    f"their model mid-stream; drain the tenant first"
                )
            slot = self.adapters.add(name, adapter)
            if self.prefix_cache is not None:
                # Adapter-keyed chains carry adapter-specific KV: a
                # replace means the resident chain no longer matches
                # the factors a future claim would decode through.
                self._prefix_drops.append(name)
        return slot

    def remove_adapter(self, name: str) -> None:
        """Free one tenant's pool slot.  Refused while any queued or
        active request references the name (a freed slot re-issued to
        a new tenant would serve the old tenant's requests the NEW
        tenant's delta — the cross-tenant corruption a serving pool
        must never allow)."""
        if self.adapters is None:
            raise ValueError("engine has no adapter pool")
        name = str(name)
        with self._lock:
            if self.scheduler.references_adapter(name):
                raise RuntimeError(
                    f"adapter {name!r} is serving queued/active "
                    f"requests — drain the tenant before removing it"
                )
            self.adapters.remove(name)
            if self.prefix_cache is not None:
                self._prefix_drops.append(name)

    def adapter_names(self) -> List[str]:
        """Loaded tenant names (the replica beat advertises these for
        adapter-aware router placement)."""
        return [] if self.adapters is None else self.adapters.names()

    def drain_done(self) -> List[Tuple[str, str]]:
        """Terminal ``(rid, status)`` pairs since the last call — the
        per-beat completion feed of a disaggregated decode replica
        (``serve/dist/replica.py``): the router prunes its in-flight
        tracking from it, which is what makes failover re-submission
        exact (a request is re-submitted iff no terminal status ever
        reached the router)."""
        with self._lock:
            items = list(self._done_feed)
            self._done_feed.clear()
        return items

    def drain_failed(self) -> List[Tuple[str, str]]:
        """Non-terminal ``(rid, error)`` handoff-admission failures
        since the last call — the beat's ``failed`` feed when
        ``report_handoff_failures`` is on.  The router treats each like
        a prefill-worker failure: re-dispatch the prefill, never a
        terminal client reply."""
        with self._lock:
            items = list(self._failed_feed)
            self._failed_feed.clear()
        return items

    def cancel(self, rid: str) -> bool:
        """Drop one request wherever it is — queued or mid-decode (the
        hedged-request first-winner cancel, and the client-abort path).
        Idempotent: unknown or already-finished rids return False.  The
        terminal status is ``cancelled`` (done feed + typed reply), so
        routers and clients prune it like any completion."""
        with self._lock:
            req = self.scheduler.cancel(rid)
            if req is None:
                return False
            handle = self._handles.pop(rid, None)
            self._done_feed.append((rid, "cancelled"))
        self.stats.bump("cancelled")
        req.finished_t = time.monotonic()
        if handle is not None:
            handle._done.set()
        reply = getattr(req, "_reply", None)
        if reply is not None:
            self._reply(reply, {
                "type": "serve_done", "rid": rid,
                "status": "cancelled", "reason": "cancelled",
                "tokens": [int(t) for t in req.generated],
            })
        return True

    # -- background thread ---------------------------------------------------
    def start(self) -> "ServeEngine":
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._serve_forever, name="rlt-serve", daemon=True
        )
        self._thread.start()
        return self

    def _serve_forever(self) -> None:
        if self.fault_member is not None:
            # The serve thread declares its fleet identity so
            # replica:-pinned faults fire here, not on whichever member
            # thread registered last (inproc fleets share one process).
            set_member(*self.fault_member)
        try:
            while not self._stop.is_set():
                try:
                    worked = self.step()
                except Exception as e:  # noqa: BLE001 - a dying loop must
                    # fail its pending work loudly, never strand it
                    self._fail_pending(e)
                    return
                if not worked:
                    ph, t0 = self._open_turn("idle")
                    try:
                        time.sleep(self.config.idle_wait_s)
                    finally:
                        self._close_turn(ph, t0, ticks=0)
        finally:
            self._drop_between()

    def _fail_pending(self, exc: BaseException) -> None:
        """The serve loop died: mark the engine dead (submit() refuses
        from now on), fail every in-flight/queued handle with the error,
        and tell queue-plane clients (``serve_done(status="error")``)
        instead of letting them block to their timeouts."""
        import logging

        self._error = exc
        with self._lock:
            handles = list(self._handles.values())
            self._handles.clear()
        logging.getLogger(__name__).error(
            "serve loop died: %r — failing %d pending request(s)",
            exc, len(handles), exc_info=exc,
        )
        for handle in handles:
            handle.error = exc
            req = handle.request
            reply = getattr(req, "_reply", None)
            if reply is not None:
                self._reply(reply, {
                    "type": "serve_done", "rid": req.rid,
                    "status": "error", "error": repr(exc),
                    "tokens": [int(t) for t in req.generated],
                })
            handle._done.set()

    def halt_loop(self) -> None:
        """Quiesce the background serve thread WITHOUT tearing the
        engine down (``stop()`` also closes reply handles, the inbox
        and exporters): the planned-drain migration path halts the
        loop, exports the resident sequences from the frozen scheduler
        (:meth:`export_resident`), then calls :meth:`stop`."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def _refuse_block_transfer(self, what: str) -> None:
        if "block_transfer" in self.family.refuses:
            raise ValueError(
                f"{what} is not supported for the {self.family.name} "
                f"family: {self.family.refuses_why} (KV handoff and "
                f"live migration move GPT's kind of block)"
            )

    def export_resident(self) -> List[dict]:
        """Export every resident decoding sequence's KV blocks plus
        scheduler position — the planned-drain live-migration payload
        (docs/FAULT_TOLERANCE.md "Serving-plane faults").  Call with
        the loop quiesced (:meth:`halt_loop`); each entry feeds
        ``make_migration_item`` and a survivor's migration admission.
        Queued requests and chunked prefills mid-flight are NOT
        exported: they have no emitted position worth moving, so the
        router's ordinary recompute failover covers them."""
        self._refuse_block_transfer("export_blocks")
        out = []
        sched = self.scheduler
        Bs = self.config.block_size
        for slot, req in enumerate(sched.slots):
            if req is None or slot in self._chunk_jobs:
                continue
            if not req.generated:
                continue
            # seq_lens[slot] == prompt + generated − 1: the final
            # sampled token's KV was never written (it is the NEXT
            # decode tick's input), so exactly ceil(seq_len/Bs) blocks
            # hold everything the survivor needs.
            seq_len = int(sched.seq_lens[slot])
            n_blocks = -(-seq_len // Bs)
            ids = sched._blocks[slot][:n_blocks]
            kv = self.cache.export_blocks(self._pool, ids)
            fields = {
                "rid": req.rid, "prompt": list(req.prompt),
                "max_new_tokens": int(req.max_new_tokens),
                "temperature": float(req.temperature),
                "eos_token_id": req.eos_token_id,
                "top_k": req.top_k,
                "adapter": req.adapter,
                "priority": int(req.priority),
                "sample_seed": req.sample_seed,
            }
            reply = getattr(req, "_reply", None)
            if reply is not None:
                fields["reply"] = list(reply)
            out.append({
                "req": fields,
                "generated": list(req.generated),
                "cur_token": int(self._cur_tokens[slot]),
                "seq_len": seq_len,
                "kv": kv,
            })
        return out

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self._watch is not None:
            self._watch.close()     # the collector hook
        if self.prefix_cache is not None:
            self.prefix_cache.drop_all()
        if self._inbox is not None:
            self._inbox.shutdown()
            self._inbox = None
        with self._lock:
            reply_handles = list(self._reply_handles.values())
            self._reply_handles.clear()
        for h in reply_handles:
            h.close()
        # Final unthrottled export: a recompile or counter bump landing
        # inside the last export_every_s window must still reach the
        # prom file / serve-live.json before teardown.
        self._maybe_export(force=True)
        if self._exporter is not None:
            self._exporter.close()
        if self._trace_dir is not None and self.tracer.events():
            import os

            try:
                os.makedirs(self._trace_dir, exist_ok=True)
                self.tracer.export_jsonl(
                    f"{self._trace_dir}/trace-serve-"
                    f"{self._trace_name}.jsonl"
                )
            except OSError:
                pass  # a full disk must not fail the teardown
        # Serve-replica teardown reclaims dead prefill handoffs: a
        # prefill worker killed -9 mid-handoff leaves rlt-kv segments
        # whose owner pid is gone and which no consumer will ever read
        # — the engine-close sweep (mirroring the router's failover
        # sweep) keeps tmpfs bounded across replica restarts.
        try:
            from ray_lightning_tpu.cluster.shm import sweep_stale_segments

            sweep_stale_segments("rlt-kv")
        except Exception:  # noqa: BLE001 - janitorial, never raises out
            pass

    # -- DriverQueue request plane ------------------------------------------
    def queue_handle(self):
        """Picklable submission handle for :class:`serve.client.
        ServeClient` — created on first use (driver-side TCP inbox)."""
        if self._inbox is None:
            from ray_lightning_tpu.cluster.queue import DriverQueue

            self._inbox = DriverQueue()
        return self._inbox.handle

    def _drain_inbox(self) -> None:
        if self._inbox is None:
            return
        import queue as _pyqueue

        while True:
            try:
                recv_t, item = self._inbox.get_nowait_stamped()
            except _pyqueue.Empty:
                break
            try:
                self._handle_queue_request(item, recv_t)
            except Exception as e:  # noqa: BLE001 - a bad request must
                # never take the serve loop down
                import logging

                logging.getLogger(__name__).warning(
                    "serve: dropped malformed queue request: %s", e
                )
        if self._deferred_inbox:
            # One retry pass per drain: each item re-defers (bounded)
            # or proceeds now that its adapter-load frame landed above.
            retry, self._deferred_inbox = self._deferred_inbox, deque()
            for item in retry:
                try:
                    self._handle_queue_request(item)
                except Exception as e:  # noqa: BLE001 - as above
                    import logging

                    logging.getLogger(__name__).warning(
                        "serve: dropped malformed queue request: %s", e
                    )

    def _handle_queue_request(self, item: dict,
                              recv_t: Optional[float] = None) -> None:
        """``recv_t`` is when the frame landed in the inbox
        (``time.monotonic()``), up to a tick before this loop got round
        to it; the ``queue_wait_us`` counter counts from there
        (``arrival_t``, and with it TTFT and deadlines, from here)."""
        if not isinstance(item, dict):
            raise ValueError(f"not a serve item: {type(item).__name__}")
        kind = item.get("type")
        if kind == "serve_adapter_load":
            # Tenant hot-load from the queue plane (router dispatch or
            # operator tooling): scatter into the pool through the ONE
            # compiled scatter program — a join-on-arrival for MODELS,
            # recompile-free like every other admission.
            self._load_adapter_item(item)
            return
        if kind == "serve_cancel":
            # Hedge loser (or client abort): drop the request wherever
            # it is — queued, decoding, or already gone (idempotent).
            self.cancel(str(item["rid"]))
            return
        if kind in ("serve_kv_handoff", "serve_migration"):
            self._refuse_block_transfer("import_blocks")
            fields = dict(item["req"])
            adapter = fields.get("adapter")
            if (adapter is not None and self.adapters is not None
                    and not self.adapters.has(str(adapter))):
                # The router's serve_adapter_load frame rides the
                # router->replica lane; the handoff arrives from the
                # prefill WORKER's own connection and can outrun it.
                # Defer on a WALL-CLOCK deadline (a drain-count bound
                # would scale with loop speed: an idle replica drains
                # every ~2ms, exhausting any count long before a
                # chunk-sent multi-MB blob lands cross-host) instead of
                # failing a valid request "unknown adapter" — checked
                # BEFORE _decode_handoff so the read-once shm payload
                # survives the retry.
                deadline = item.get("_adapter_wait_deadline")
                if deadline is None:
                    deadline = time.monotonic() + 10.0
                    item["_adapter_wait_deadline"] = deadline
                    item["_recv_t"] = recv_t     # for the retry pass
                if time.monotonic() < deadline:
                    self._deferred_inbox.append(item)
                    return
        elif kind == "serve_request":
            fields = item
        else:
            raise ValueError(f"not a serve request/handoff: {kind!r}")
        rid = str(item["rid"])
        reply = tuple(fields["reply"])  # (host, port)
        if kind == "serve_migration":
            self._admit_migration(item, fields, rid, reply)
            return
        if item.get("hedge"):
            # Hedged duplicate that reached a single engine directly
            # (no router to place it on a SECOND replica): drop it —
            # the primary admission is already decoding this rid, and
            # a duplicate here would double-book the slot.
            with self._lock:
                if rid in self._handles:
                    return

        def on_token(i: int, tok: int) -> None:
            self._reply(reply, {
                "type": "serve_token", "rid": rid, "index": i,
                "token": int(tok),
            })

        try:
            if kind == "serve_kv_handoff":
                _fault_fire("handoff_read", rid=rid,
                            path=item.get("shm"))
            handoff = (self._decode_handoff(item)
                       if kind == "serve_kv_handoff" else None)
            trace_ctx = None
            if self.tracer.enabled:
                from ray_lightning_tpu.telemetry.propagate import (
                    extract, sent_ts,
                )

                # The request body carries the ROUTER-stamped context
                # (the trace root); a handoff envelope additionally
                # carries the prefill worker's span + send time.  The
                # transfer interval is booked HERE — at read — so it
                # ends where queue_wait begins (booking it at admission
                # would fold the slot backlog into "transfer" and
                # double-count it against queue_wait).
                trace_ctx = extract(fields)
                if handoff is not None:
                    h_sent = sent_ts(item)
                    if h_sent is not None and trace_ctx is not None:
                        h_dur = max(0.0, time.time() - h_sent)
                        self.tracer.record(
                            "handoff_transfer", h_sent, h_dur,
                            args=trace_args(
                                child_context(extract(item)
                                              or trace_ctx),
                                rid=rid,
                            ),
                        )
                        self.stats.note_phase("handoff_transfer",
                                              h_dur)
            handle = self.submit(
                fields["prompt"], int(fields["max_new_tokens"]),
                temperature=float(fields.get("temperature", 0.0)),
                eos_token_id=fields.get("eos_token_id"),
                top_k=fields.get("top_k"),
                spec=fields.get("spec"),
                adapter=fields.get("adapter"),
                deadline_s=fields.get("deadline_s"),
                sample_seed=fields.get("sample_seed"),
                on_token=on_token, rid=rid, _handoff=handoff,
                _trace_ctx=trace_ctx,
                _recv_t=(recv_t if recv_t is not None
                         else item.get("_recv_t")),
            )
        except FaultBlackhole:
            # Injected network partition on the read side: the frame
            # just never arrived.  No reply, no feed entry — recovery
            # is the router's beat-loss/claim machinery, exactly as for
            # a real partition.
            return
        except (ValueError, TypeError, KeyError, OSError,
                FaultInjected) as e:
            # TypeError covers malformed field coercion (int(None), ...);
            # KeyError/OSError cover a torn handoff payload or a segment
            # that vanished before the read (TTL-pruned after a very
            # slow handoff, swept by a teardown, or a path from another
            # host): once the reply address is known, every bad request
            # gets the typed "invalid" reply — a silent drop would leave
            # the client blocking to its timeout AND the router counting
            # a phantom in-flight request against this replica forever.
            # The done feed carries the terminal status so a router
            # prunes it like any other.
            if kind == "serve_kv_handoff" and self.report_handoff_failures:
                # Disaggregated replica: a torn/vanished payload is the
                # PREFILL's failure, not the request's — report it on
                # the beat's failed feed so the router re-dispatches
                # the prefill (same recovery as a worker death) instead
                # of failing the client terminally.
                with self._lock:
                    self._failed_feed.append((rid, repr(e)))
                return
            with self._lock:
                self._done_feed.append((rid, "invalid"))
            self._reply(reply, {
                "type": "serve_done", "rid": rid, "status": "invalid",
                "error": str(e), "tokens": [],
            })
            return
        handle.request._reply = reply
        if handle.status == "rejected":
            self._reply_done(handle.request)

    def _load_adapter_item(self, item: dict) -> None:
        """One ``serve_adapter_load`` frame: resolve the chunked-bytes
        / tmpfs-segment payload (same dual transport as KV handoffs)
        and add the tenant.  Raises on pool-less engines or malformed
        payloads — ``_drain_inbox`` logs and drops, and the tenant's
        subsequent requests come back as typed ``invalid`` replies
        ("unknown adapter"), so a failed load is never silent."""
        from ray_lightning_tpu.serve.lora import decode_adapter

        if self.adapters is None:
            raise ValueError(
                "serve_adapter_load on an engine without an adapter "
                "pool (ServeConfig.max_adapters == 0) — router caps "
                "should have excluded this replica"
            )
        _fault_fire("adapter_load", rid=str(item.get("name", "")))
        self.add_adapter(str(item["name"]), decode_adapter(item))

    def _decode_handoff(self, item: dict) -> dict:
        """Decode a ``serve_kv_handoff`` frame's ``{"kv", "logits"}``
        payload (shm segments are read once and unlinked —
        consumer-owned lifetime).  Geometry drift between the prefill
        worker and this replica is a deploy bug and fails the request
        loudly (typed ``invalid`` reply upstream)."""
        # Runtime import (the dist package imports this module at its
        # own import time); decode_kv_payload is the one inverse of the
        # worker's encode_kv_payload — an encoding change lands on both
        # sides or neither.
        from ray_lightning_tpu.serve.dist.handoff import decode_kv_payload

        tree = decode_kv_payload(item)
        bucket = int(item["bucket"])
        n_blocks = int(tree["kv"]["k"].shape[1])
        expect = self.scheduler.bucket_for(int(item["prompt_len"]))
        if bucket != expect or n_blocks * self.config.block_size != bucket:
            raise ValueError(
                f"kv handoff geometry mismatch: worker bucket {bucket} "
                f"({n_blocks} blocks of {self.config.block_size}) vs "
                f"replica bucket {expect} — prefill worker and decode "
                f"replica must share block_size/bucket config"
            )
        return tree

    def _admit_migration(self, item: dict, fields: dict, rid: str,
                         reply: Tuple[str, int]) -> None:
        """One ``serve_migration`` frame: adopt a draining replica's
        resident sequence mid-decode — import its KV blocks, seat the
        request with its emitted history, and continue decode at the
        exact position the source stopped.  Zero recomputed prefill;
        the position-keyed sampler keeps the continued stream
        bitwise-identical at any temperature.  Any adoption failure
        (pool dry, geometry drift, torn payload) falls back to the
        recompute path: a fresh submit with the same fleet seed replays
        the identical stream and the client dedups re-emitted
        indices."""

        def on_token(i: int, tok: int) -> None:
            self._reply(reply, {
                "type": "serve_token", "rid": rid, "index": i,
                "token": int(tok),
            })

        try:
            adopted = self._adopt_migration(item, fields, rid, reply,
                                            on_token)
        except (ValueError, TypeError, KeyError, OSError,
                FaultInjected) as e:
            import logging

            logging.getLogger(__name__).warning(
                "serve: migration adopt failed for %s (%s) — "
                "recompute fallback", rid, e,
            )
            adopted = False
        if adopted:
            return
        try:
            handle = self.submit(
                fields["prompt"], int(fields["max_new_tokens"]),
                temperature=float(fields.get("temperature", 0.0)),
                eos_token_id=fields.get("eos_token_id"),
                top_k=fields.get("top_k"),
                adapter=fields.get("adapter"),
                sample_seed=fields.get("sample_seed"),
                on_token=on_token, rid=rid,
            )
        except (ValueError, TypeError, KeyError, OSError) as e:
            with self._lock:
                self._done_feed.append((rid, "invalid"))
            self._reply(reply, {
                "type": "serve_done", "rid": rid, "status": "invalid",
                "error": str(e), "tokens": [],
            })
            return
        handle.request._reply = reply
        if handle.status == "rejected":
            self._reply_done(handle.request)

    def _adopt_migration(self, item: dict, fields: dict, rid: str,
                         reply: Tuple[str, int], on_token) -> bool:
        """Seat one migrated sequence.  True = adopted (decode resumes
        at ``seq_len`` next tick); False = resources unavailable (no
        free slot / pool dry / no matching import width) — the caller
        falls back to recompute.  Malformed payloads raise and fall
        back the same way."""
        import jax.numpy as jnp

        from ray_lightning_tpu.serve.dist.handoff import decode_kv_payload
        from ray_lightning_tpu.serve.scheduler import Request

        sched = self.scheduler
        Bs = self.config.block_size
        prompt = [int(t) for t in fields["prompt"]]
        generated = [int(t) for t in item["generated"]]
        max_new = int(fields["max_new_tokens"])
        seq_len = int(item["seq_len"])
        cur_token = int(item["cur_token"])
        if not generated or len(generated) >= max_new:
            raise ValueError(
                "migration carries no live decode position"
            )
        if seq_len != len(prompt) + len(generated) - 1:
            raise ValueError(
                f"migration position mismatch: seq_len {seq_len} != "
                f"prompt {len(prompt)} + generated {len(generated)} - 1"
            )
        if len(prompt) + max_new > self.max_model_len:
            raise ValueError(
                f"migrated request exceeds max_model_len "
                f"({self.max_model_len})"
            )
        sample_seed = fields.get("sample_seed")
        if sample_seed is None:
            raise ValueError(
                "migration without a sample_seed — the continued "
                "stream would not replay the source's"
            )
        n_blocks = -(-seq_len // Bs)
        kv = decode_kv_payload(item)["kv"]
        if int(kv["k"].shape[1]) != n_blocks:
            raise ValueError(
                f"migration payload carries {int(kv['k'].shape[1])} "
                f"blocks, position {seq_len} needs {n_blocks} — "
                f"source and survivor must share block_size"
            )
        ids = sched._alloc(n_blocks)
        if ids is None:
            return False
        ok = False
        try:
            # Scatter through the SAME per-block-count executables the
            # bucketed handoff imports compiled (greedy decomposition
            # into bucket block counts) — a migration admission never
            # adds a program variant, so steady-state recompiles stay
            # pinned at zero on the survivor.
            sizes = sorted({b // Bs for b in sched.buckets},
                           reverse=True)
            off = 0
            while off < n_blocks:
                c = next((s for s in sizes if s <= n_blocks - off),
                         None)
                if c is None:
                    return False  # bucket set can't tile the remainder
                chunk = jnp.asarray(
                    np.asarray(ids[off: off + c], np.int32)
                )
                payload = {k: jnp.asarray(v[:, off: off + c])
                           for k, v in kv.items()}
                self._pool = self._import_fn(self._pool, payload, chunk)
                off += c
            req = Request(
                rid=rid, prompt=prompt, max_new_tokens=max_new,
                temperature=float(fields.get("temperature", 0.0)),
                eos_token_id=fields.get("eos_token_id"),
                top_k=fields.get("top_k"),
                # The draft cache never saw this prefix: plain decode
                # only.  _spec_tick at width 0 emits exactly the plain
                # position-keyed token, so mixed ticks stay bitwise.
                spec=0,
                adapter=fields.get("adapter"),
                priority=int(fields.get("priority", 0)),
                sample_seed=int(sample_seed),
                on_token=on_token,
            )
            req.generated = generated
            handle = ServeHandle(rid, req)
            with self._lock:
                if req.adapter is not None:
                    if self.adapters is None:
                        raise ValueError(
                            f"migrated request names adapter "
                            f"{req.adapter!r} but this engine has no "
                            f"adapter pool"
                        )
                    try:
                        req._adapter_slot = self.adapters.slot_of(
                            req.adapter
                        )
                    except KeyError:
                        raise ValueError(
                            f"unknown adapter {req.adapter!r} on the "
                            f"migration survivor"
                        ) from None
                slot = sched.adopt(req, ids, seq_len)
                if slot is None:
                    return False
                self.stats.bump("submitted")
                self._handles[rid] = handle
            self._cur_tokens[slot] = cur_token
            req._reply = reply
            ok = True
            return True
        finally:
            if not ok:
                sched.allocator.free(ids)

    def _reply_done(self, req) -> None:
        reply = getattr(req, "_reply", None)
        if reply is None:
            return
        self._reply(reply, {
            "type": "serve_done", "rid": req.rid,
            "status": req.state.value,
            "reason": req.done_reason,
            "tokens": [int(t) for t in req.generated],
        })

    @contextlib.contextmanager
    def _coalesced_replies(self):
        """Hold the replies this thread makes inside the block (a
        tick's emit loop) and send them on leaving it: one
        ``serve_batch`` frame per reply address, items in order; a lone
        item goes bare."""
        if not self.config.coalesce_replies or self._reply_batch is not None:
            yield
            return
        held: Dict[Tuple[str, int], List[dict]] = {}
        self._reply_batch = (threading.get_ident(), held)
        try:
            yield
        finally:
            self._reply_batch = None
            for addr, items in held.items():
                self._reply(addr, items[0] if len(items) == 1 else
                            {"type": "serve_batch", "items": items})

    def _reply(self, addr: Tuple[str, int], item: dict) -> None:
        from ray_lightning_tpu.cluster.queue import QueueHandle

        batch = self._reply_batch
        if batch is not None and batch[0] == threading.get_ident():
            batch[1].setdefault(addr, []).append(item)
            return
        with self._lock:
            handle = self._reply_handles.get(addr)
            if handle is None:
                handle = QueueHandle(addr[0], addr[1])
                self._reply_handles[addr] = handle
        try:
            handle.put(item)
            self.stats.bump("reply_frames")
        except (OSError, ConnectionError):
            # Client went away: drop its stream, keep serving others.
            with self._lock:
                self._reply_handles.pop(addr, None)

    # -- telemetry -----------------------------------------------------------
    def _refresh_gauges(self) -> None:
        gauges = self.scheduler.snapshot()
        if self.adapters is not None:
            pool = self.adapters.snapshot()
            gauges["lora_adapters_loaded"] = pool["loaded"]
            gauges["lora_slots_free"] = pool["slots_free"]
            counts = [t for t in
                      self.stats.adapter_token_counts().values() if t]
            # Fairness spread: min/max lifetime tokens across tenants
            # with traffic (1.0 = perfectly fair; the DRR grant policy
            # keeps this near 1 under uniform per-tenant load).
            gauges["lora_fairness_spread"] = (
                min(counts) / max(counts) if len(counts) > 1 else 1.0
            )
        if self.prefix_cache is not None:
            ps = self.prefix_cache.stats()
            hit_rate = (ps["hits"] / ps["lookups"]) if ps["lookups"] \
                else 0.0
            gauges["prefix_cache_hit_rate"] = hit_rate
            gauges["prefix_cached_blocks"] = ps["cached_blocks"]
            self.stats.set_prefix(
                hit_rate=hit_rate, lookups=ps["lookups"],
                hits=ps["hits"],
                blocks_claimed=ps["blocks_claimed"],
                blocks_inserted=ps["blocks_inserted"],
                blocks_evicted=ps["blocks_evicted"],
                cached_blocks=ps["cached_blocks"],
            )
        if self.spec_k > 0:
            counters = self.stats.counters
            drafted = counters.get("spec_drafted", 0)
            gauges["spec_acceptance_rate"] = (
                counters.get("spec_accepted", 0) / drafted if drafted
                else 0.0
            )
            elapsed = max(time.monotonic() - self._started_t, 1e-9)
            # Goodput = EMITTED tokens/s — what clients actually see,
            # vs the drafted+verified work the chip performed.
            gauges["spec_goodput_tokens_per_sec"] = (
                counters.get("spec_emitted", 0) / elapsed
            )
        self.stats.set_gauges(**gauges)

    @property
    def capacity_oracle(self):
        """The headroom oracle (``serve/capacity.py``) when the
        capacity plane is on, else None."""
        return self._capacity

    @property
    def slo_evaluator(self):
        """The burn-rate evaluator (``telemetry/slo.py``) when the SLO
        plane is on, else None."""
        return self._slo

    @property
    def slo_alerts(self) -> List[dict]:
        """Fired ``slo_alert`` events (bounded ring, newest last)."""
        return list(self._slo_alerts)

    def snapshot(self) -> dict:
        """The live serve snapshot (schema:
        ``telemetry/schema.py::validate_serve_snapshot``).  On
        capacity-plane engines the newest headroom-oracle block rides
        the ``capacity`` key — beats built from this snapshot carry it
        to the router for free."""
        snap = self.stats.snapshot()
        if self._capacity is not None and self._capacity.last is not None:
            snap["capacity"] = dict(self._capacity.last)
        return snap

    def _maybe_export(self, force: bool = False) -> None:
        if self._exporter is None and self._live_path is None \
                and self._capacity is None:
            return
        now = time.monotonic()
        if not force and now - self._last_export < self.config.export_every_s:
            return
        self._last_export = now
        if self._capacity is not None:
            # The SLO/capacity plane ticks here, on the CHEAP stats
            # slice (counters + gauges + recent queue-wait p50) — the
            # full snapshot sorts four 4096-sample reservoirs, too
            # heavy for a sub-second tick under the plane's <2%
            # overhead budget.  Recompiles ride the compile-event
            # counter, NOT a ledger snapshot (which walks every
            # program's cost rows).
            from ray_lightning_tpu.telemetry import compile_event_count

            self._capacity.observe(
                self.stats.capacity_view(),
                recompiles=int(compile_event_count()),
            )
            if force or now - self._last_capacity >= self._capacity_every_s:
                self._last_capacity = now
                self._capacity.snapshot()  # caches on .last
        if self._slo is not None:
            fired = self._slo.evaluate()
            if fired:
                self.stats.bump("slo_alerts", len(fired))
        if self._exporter is None and self._live_path is None:
            return
        snap = self.stats.snapshot()
        if self._capacity is not None and self._capacity.last is not None:
            snap["capacity"] = dict(self._capacity.last)
        # The program ledger rides every real export: rlt_program_*
        # gauges on the prom side, the programs pane on the rlt_top
        # side.
        from ray_lightning_tpu.telemetry import program_ledger

        payload = {"serve": snap, "programs": program_ledger.snapshot()}
        if self._slo is not None:
            payload["slo"] = self._slo.snapshot()
        if self._exporter is not None:
            self._exporter.update(payload)
        if self._live_path is not None:
            import json
            import os

            tmp = self._live_path + ".tmp"
            try:
                with open(tmp, "w") as f:
                    json.dump({"ts": snap["ts"], **payload}, f)
                os.replace(tmp, self._live_path)
            except OSError:
                pass  # a full disk must not take the serve loop down
