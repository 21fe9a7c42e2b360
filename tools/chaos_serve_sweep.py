"""Serving-plane chaos sweep: the fault x recovery matrix end-to-end.

Two modes (mirrors ``tools/chaos_sweep.py``, which owns the TRAINING
fault matrix — this tool owns the serving plane):

* ``--selftest`` (wired into ``format.sh`` layer 5): fast, jax-free
  checks of the sweep's own machinery — every matrix cell's
  ``RLT_FAULT`` string parses, the brownout ladder's hysteresis and
  half-open probe logic and the client retry policy's backoff maths.
* default: the full serving matrix — for each cell a real inproc
  fleet (2 decode replicas, prefill workers where the cell needs
  them) with the fault injected deterministically, asserting the
  affected streams complete with BITWISE parity against an
  uninterrupted single-engine reference, zero lost requests, and the
  cell's recovery counters.  Exits non-zero on any unrecovered cell.

The matrix::

    drain-migration   planned drain -> live KV migration (zero
                      recomputed prefill, parity at temperature>0)
    kill-failover     abrupt death  -> recompute failover + dedup
    blackhole-beat    beat partition -> beat-loss failover while the
                      victim's stream keeps racing (client dedup)
    torn-handoff      torn prefill handoff payload -> failed-feed
                      re-dispatch
    shm-vanish        KV segment unlinked between send and read ->
                      failed-feed re-dispatch
    slow-hedge        straggler replica -> hedged resubmit, first
                      winner, loser cancelled
    brownout          sustained overload -> ladder climbs to shed
                      (typed replies, priority traffic survives),
                      recovery descends and re-admits

Usage::

    python tools/chaos_serve_sweep.py --selftest
    python tools/chaos_serve_sweep.py                 # full matrix
    python tools/chaos_serve_sweep.py --only drain-migration
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_P1 = list(range(1, 9))
_P2 = list(range(9, 17))
_MAX_NEW = 30


# ---------------------------------------------------------------------------
# --selftest: the sweep's own machinery (no jax, no fleets)
# ---------------------------------------------------------------------------

#: Every fault template a matrix cell injects ("{member}" is filled
#: with the discovered victim id at run time).
_CELL_FAULTS = {
    "blackhole-beat": "blackhole@point:beat,replica:{member},once:0",
    "torn-handoff": "exc@point:handoff_read,nth:1",
    "shm-vanish": "shm_vanish@point:handoff_send,nth:1",
    "slow-hedge": "slow@point:replica_tick,replica:{member},secs:0.4,once:0",
}


def _selftest() -> list:
    problems: list = []

    def check(cond: bool, what: str) -> None:
        if not cond:
            problems.append(what)

    # Every cell's grammar must parse (a typo'd spec silently matches
    # nothing and "proves" recovery paths that never fired).
    from ray_lightning_tpu.fault import inject

    for name, tmpl in _CELL_FAULTS.items():
        try:
            specs = inject.parse_faults(tmpl.format(member="r0"))
            check(len(specs) == 1, f"{name}: expected 1 spec")
        except ValueError as e:
            problems.append(f"{name}: fault template does not parse: {e}")

    # Brownout ladder: one-rung moves, hysteresis, dwell, probe.
    from ray_lightning_tpu.serve.brownout import BrownoutLadder

    t = [0.0]
    ladder = BrownoutLadder(min_dwell_s=1.0, probe_every_s=5.0,
                            clock=lambda: t[0])
    check(ladder.observe(0.99) == 1, "ladder: first climb not immediate")
    check(ladder.observe(2.0) == 1, "ladder: climbed without dwell")
    t[0] = 1.0
    check(ladder.observe(0.96) == 2, "ladder: rung 2 climb")
    t[0] = 2.0
    check(ladder.observe(1.0) == 3, "ladder: rung 3 climb")
    t[0] = 3.0
    check(ladder.observe(0.96) == 3, "ladder: descended above exit")
    check(ladder.observe(0.80) == 2, "ladder: rung 3 -> 2 descent")
    t[0] = 4.0
    check(ladder.observe(0.10) == 1, "ladder: rung 2 -> 1 descent")
    t[0] = 5.0
    check(ladder.observe(0.10) == 0, "ladder: rung 1 -> 0 descent")
    check(ladder.allow_probe() is True, "ladder: first probe denied")
    check(ladder.allow_probe() is False, "ladder: probe window ignored")
    t[0] = 11.0
    check(ladder.allow_probe() is True, "ladder: probe never re-armed")
    for bad_kwargs in ({"enter": (0.9, 0.8, 1.0)}, {"exit_margin": 0.0},
                       {"max_new_cap": 0}, {"enter": (0.5, 0.9)}):
        try:
            BrownoutLadder(**bad_kwargs)
            problems.append(f"ladder: {bad_kwargs} should not construct")
        except ValueError:
            pass

    # Client retry policy: env resolution and the backoff series.
    from ray_lightning_tpu.serve.client import RetryPolicy

    os.environ["RLT_RETRY_MAX"] = "5"
    os.environ["RLT_RETRY_BACKOFF_S"] = "0.2"
    os.environ["RLT_HEDGE"] = "1"
    try:
        pol = RetryPolicy.from_env()
        check(pol.max_attempts == 5 and pol.backoff_s == 0.2
              and pol.hedge is True, "retry: env resolution")
    finally:
        for k in ("RLT_RETRY_MAX", "RLT_RETRY_BACKOFF_S", "RLT_HEDGE"):
            os.environ.pop(k, None)
    pol = RetryPolicy(backoff_s=0.05, backoff_max_s=0.3)
    pauses = [min(pol.backoff_max_s, pol.backoff_s * 2 ** (a - 1))
              for a in range(1, 5)]
    check(pauses == [0.05, 0.1, 0.2, 0.3], f"retry: backoff series {pauses}")
    return problems


# ---------------------------------------------------------------------------
# Full matrix: real inproc fleets with injected faults
# ---------------------------------------------------------------------------

_MODEL = None
_REF = None


def _model():
    """One tiny GPT, built once and reused by every cell."""
    global _MODEL
    if _MODEL is None:
        import jax

        from ray_lightning_tpu.models.gpt import GPT, GPTConfig

        cfg = GPTConfig(vocab_size=128, n_layer=2, n_head=4, d_model=64,
                        seq_len=64, warmup_steps=1)
        m = GPT(cfg, attn_impl="xla")
        _MODEL = (m, m.init_params(jax.random.PRNGKey(0)))
    return _MODEL


def _serve_cfg():
    from ray_lightning_tpu.serve.engine import ServeConfig

    return ServeConfig(num_slots=2, block_size=8)


def _reference():
    """Uninterrupted single-engine token streams — the parity pin."""
    global _REF
    if _REF is None:
        from ray_lightning_tpu.serve.engine import ServeEngine

        m, params = _model()
        eng = ServeEngine(m, params, _serve_cfg())
        _REF = (eng.generate(_P1, _MAX_NEW, temperature=0.7),
                eng.generate(_P2, _MAX_NEW))
        eng.stop()
    return _REF


def _await(cond, timeout_s: float, poll_s: float = 0.01) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll_s)
    return False


def _row(name: str) -> dict:
    return {"name": name, "ok": False, "error": "", "ttr_s": None,
            "re_emitted": 0, "parity": None, "lost": 0, "notes": "",
            "wall_s": 0.0}


def _launch(n_prefill: int = 0, **router_kwargs):
    from ray_lightning_tpu.serve.client import ServeClient
    from ray_lightning_tpu.serve.dist import launch_inproc_fleet

    m, params = _model()
    fleet = launch_inproc_fleet(
        m, params, _serve_cfg(), n_replicas=2, n_prefill=n_prefill,
        lost_after_s=0.5, **router_kwargs,
    )
    return fleet, ServeClient(fleet.queue_handle())


def _stream_started(fleet, client, rid, min_tokens: int = 3):
    """Wait until ``rid`` is placed and has streamed a few tokens;
    returns its replica id."""

    def started():
        track = fleet.router._inflight.get(rid)
        return (track is not None and track.replica is not None
                and len(client._pending[rid].tokens) >= min_tokens)

    if not _await(started, 60.0):
        raise RuntimeError(f"{rid} never started streaming")
    return fleet.router._inflight[rid].replica


def _finish(row, client, fleet, rids, ref, t_disturb=None):
    """Collect results, book parity / dedup / TTR / loss into the row."""
    outs = []
    for rid in rids:
        try:
            outs.append(client.result(rid, timeout=120))
        except Exception as e:  # noqa: BLE001 - booked as a lost request
            row["lost"] += 1
            row["error"] = f"{rid}: {type(e).__name__}: {e}"
            outs.append(None)
    row["parity"] = all(
        o is not None and o == r for o, r in zip(outs, ref)
    )
    row["re_emitted"] = client.re_emitted_tokens
    if not row["parity"] and not row["error"]:
        row["error"] = "token stream diverged from the reference"
    return outs


def _steady_state_recompiles(fleet, client) -> int:
    """Post-recovery wave: a second request pair must reuse every
    compiled program: the recovery path left no cold executables
    behind."""
    from ray_lightning_tpu.telemetry import compile_event_count

    before = compile_event_count()
    r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
    r2 = client.submit(_P2, _MAX_NEW)
    client.result(r1, timeout=120)
    client.result(r2, timeout=120)
    return compile_event_count() - before


def _cell_drain_migration() -> dict:
    """Planned drain: live KV migration, zero recomputed prefill."""
    row = _row("drain-migration")
    t0 = time.monotonic()
    os.environ["RLT_MIGRATE_ON_DRAIN"] = "1"
    fleet, client = _launch()
    try:
        r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
        r2 = client.submit(_P2, _MAX_NEW)
        victim = _stream_started(fleet, client, r1)
        n_at_kill = len(client._pending[r1].tokens)
        t_kill = time.monotonic()
        next(r for r in fleet.replicas if r.id == victim).kill(hard=False)
        if _await(lambda: len(client._pending[r1].tokens) > n_at_kill,
                  60.0):
            row["ttr_s"] = round(time.monotonic() - t_kill, 3)
        _finish(row, client, fleet, (r1, r2), _reference())
        c = fleet.router.counters
        steady = _steady_state_recompiles(fleet, client)
        row["notes"] = (f"migrations={c['migrations']} "
                        f"failovers={c['failovers']} "
                        f"steady_recompiles={steady}")
        if not row["error"]:
            if c["migrations"] < 1:
                row["error"] = "no migration frame landed"
            elif c["failovers"]:
                row["error"] = "drain fell back to recompute failover"
            elif row["re_emitted"]:
                row["error"] = (
                    f"{row['re_emitted']} re-emitted tokens — "
                    "prefill was recomputed"
                )
            elif steady:
                row["error"] = f"{steady} steady-state recompiles"
            else:
                row["ok"] = True
    except Exception as e:  # noqa: BLE001 - scorecard, not traceback
        row["error"] = f"{type(e).__name__}: {e}"
    finally:
        os.environ.pop("RLT_MIGRATE_ON_DRAIN", None)
        client.close()
        fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


def _cell_kill_failover() -> dict:
    """Abrupt death: recompute failover, client dedups re-emits."""
    row = _row("kill-failover")
    t0 = time.monotonic()
    fleet, client = _launch()
    try:
        r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
        r2 = client.submit(_P2, _MAX_NEW)
        victim = _stream_started(fleet, client, r1)
        n_at_kill = len(client._pending[r1].tokens)
        t_kill = time.monotonic()
        next(r for r in fleet.replicas if r.id == victim).kill(hard=True)
        if _await(lambda: len(client._pending[r1].tokens) > n_at_kill,
                  60.0):
            row["ttr_s"] = round(time.monotonic() - t_kill, 3)
        _finish(row, client, fleet, (r1, r2), _reference())
        c = fleet.router.counters
        steady = _steady_state_recompiles(fleet, client)
        row["notes"] = (f"failovers={c['failovers']} "
                        f"re_emitted={row['re_emitted']} "
                        f"steady_recompiles={steady}")
        if not row["error"]:
            if c["failovers"] < 1:
                row["error"] = "death never failed over"
            elif steady:
                row["error"] = f"{steady} steady-state recompiles"
            else:
                row["ok"] = True
    except Exception as e:  # noqa: BLE001
        row["error"] = f"{type(e).__name__}: {e}"
    finally:
        client.close()
        fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


def _cell_blackhole_beat() -> dict:
    """Beat partition: the victim keeps streaming while the router
    (rightly) fails over — exactly-once tokens via client dedup."""
    row = _row("blackhole-beat")
    t0 = time.monotonic()
    fleet, client = _launch()
    try:
        r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
        r2 = client.submit(_P2, _MAX_NEW)
        victim = _stream_started(fleet, client, r1)
        os.environ["RLT_FAULT"] = (
            _CELL_FAULTS["blackhole-beat"].format(member=victim)
        )
        if not _await(
                lambda: fleet.router.counters["failovers"] >= 1, 30.0):
            row["error"] = "partitioned replica never declared lost"
        _finish(row, client, fleet, (r1, r2), _reference())
        c = fleet.router.counters
        row["notes"] = (f"failovers={c['failovers']} "
                        f"re_emitted={row['re_emitted']}")
        if not row["error"]:
            row["ok"] = True
    except Exception as e:  # noqa: BLE001
        row["error"] = f"{type(e).__name__}: {e}"
    finally:
        os.environ.pop("RLT_FAULT", None)
        client.close()
        fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


def _cell_torn_handoff() -> dict:
    """Torn prefill handoff payload: the replica reports the rid on
    its failed feed and the router re-dispatches the prefill."""
    row = _row("torn-handoff")
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="rlt_serve_torn_") as tmp:
        os.environ["RLT_FAULT"] = _CELL_FAULTS["torn-handoff"]
        os.environ["RLT_FAULT_STATE"] = tmp
        fleet, client = _launch(n_prefill=1)
        try:
            r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
            r2 = client.submit(_P2, _MAX_NEW)
            _finish(row, client, fleet, (r1, r2), _reference())
            row["notes"] = (
                f"resubmits={sum(t.resubmits for t in fleet.router._inflight.values())}"
            )
            if not row["error"]:
                row["ok"] = True
        except Exception as e:  # noqa: BLE001
            row["error"] = f"{type(e).__name__}: {e}"
        finally:
            os.environ.pop("RLT_FAULT", None)
            os.environ.pop("RLT_FAULT_STATE", None)
            client.close()
            fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


def _cell_shm_vanish() -> dict:
    """KV tmpfs segment unlinked between handoff send and read: the
    consumer's read fails retryably and the router re-dispatches."""
    row = _row("shm-vanish")
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="rlt_serve_shm_") as tmp:
        os.environ["RLT_FAULT"] = _CELL_FAULTS["shm-vanish"]
        os.environ["RLT_FAULT_STATE"] = tmp
        fleet, client = _launch(n_prefill=1)
        try:
            # Force the shm transport for every payload size so the
            # vanish has a segment to hit (inproc fleet = same host).
            for w in fleet.workers:
                w.runner._shm_threshold = 1
            r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
            r2 = client.submit(_P2, _MAX_NEW)
            _finish(row, client, fleet, (r1, r2), _reference())
            if not row["error"]:
                row["ok"] = True
        except Exception as e:  # noqa: BLE001
            row["error"] = f"{type(e).__name__}: {e}"
        finally:
            os.environ.pop("RLT_FAULT", None)
            os.environ.pop("RLT_FAULT_STATE", None)
            client.close()
            fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


def _cell_slow_hedge() -> dict:
    """Straggler replica: a hedged resubmit races a second replica,
    the first terminal beat wins, the loser is cancelled."""
    row = _row("slow-hedge")
    t0 = time.monotonic()
    fleet, client = _launch()
    try:
        r1 = client.submit(_P1, _MAX_NEW, temperature=0.7)
        victim = _stream_started(fleet, client, r1, min_tokens=1)
        os.environ["RLT_FAULT"] = (
            _CELL_FAULTS["slow-hedge"].format(member=victim)
        )
        if not client.hedge(r1):
            row["error"] = "hedge resubmit refused"
        _finish(row, client, fleet, (r1,), _reference()[:1])
        c = fleet.router.counters
        # The client's result arrives on the direct reply socket; the
        # router only learns the winner from the next done beat, so
        # give the beat-driven loser cancel a moment to land.
        _await(lambda: c["hedge_cancels"] >= 1, 15.0)
        row["notes"] = (f"hedges={c['hedges']} "
                        f"hedge_cancels={c['hedge_cancels']} "
                        f"re_emitted={row['re_emitted']}")
        if not row["error"]:
            if c["hedges"] < 1:
                row["error"] = "router never placed the hedge"
            elif c["hedge_cancels"] < 1:
                row["error"] = "losing copy was never cancelled"
            else:
                row["ok"] = True
    except Exception as e:  # noqa: BLE001
        row["error"] = f"{type(e).__name__}: {e}"
    finally:
        os.environ.pop("RLT_FAULT", None)
        client.close()
        fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


def _cell_brownout() -> dict:
    """Sustained overload: the ladder climbs to shed, best-effort
    traffic gets typed retryable replies while priority traffic
    admits; recovery descends and re-admits the retried request."""
    from ray_lightning_tpu.serve.brownout import BrownoutLadder
    from ray_lightning_tpu.serve.client import ServeRejected
    from ray_lightning_tpu.serve.dist.handoff import make_beat_item

    row = _row("brownout")
    t0 = time.monotonic()
    fleet, client = _launch(
        brownout=BrownoutLadder(min_dwell_s=0.0, probe_every_s=600.0),
    )

    def _forge_util(tokens_per_s: float, target_level: int) -> bool:
        """Feed the router capacity evidence over the REAL beat wire
        (the ladder only moves on evidence) until it reaches the
        target level."""

        def push_and_check():
            fleet.router.beat_handle.put(make_beat_item(
                "decode", "r0",
                snapshot={"capacity": {
                    "tokens_per_s": tokens_per_s,
                    "capacity_tokens_per_s": 100.0,
                }},
            ))
            snap = fleet.router.snapshot()
            return snap.get("brownout_level") == target_level

        return _await(push_and_check, 30.0, poll_s=0.05)

    try:
        if not _forge_util(100.0, 3):
            raise RuntimeError("ladder never climbed to shed")
        # First best-effort request IS the half-open probe (admitted by
        # contract); the second must get the typed shed reply.
        probe = client.submit(_P1, _MAX_NEW, temperature=0.7, priority=0)
        shed_rid = client.submit(_P2, _MAX_NEW, priority=0)
        try:
            client.result(shed_rid, timeout=30)
            row["error"] = "best-effort request admitted at shed level"
        except ServeRejected:
            pass
        # Priority traffic still admits at level 3.
        prio = client.submit(_P2, _MAX_NEW, priority=1)
        out_probe = client.result(probe, timeout=120)
        out_prio = client.result(prio, timeout=120)
        ref = _reference()
        row["parity"] = (out_probe == ref[0] and out_prio == ref[1])
        if not row["parity"]:
            row["error"] = "admitted streams diverged from the reference"
        # Recovery: low-utilization evidence descends the ladder and
        # the retried best-effort request admits again.
        if not row["error"] and not _forge_util(0.0, 0):
            row["error"] = "ladder never recovered to healthy"
        if not row["error"]:
            retried = client.submit(_P2, _MAX_NEW, priority=0)
            if client.result(retried, timeout=120) != ref[1]:
                row["error"] = "post-recovery retry diverged"
        c = fleet.router.counters
        row["notes"] = (f"shed={c['shed']} "
                        f"level_max=3")
        if not row["error"]:
            if c["shed"] < 1:
                row["error"] = "no typed shed reply was counted"
            else:
                row["ok"] = True
    except Exception as e:  # noqa: BLE001
        row["error"] = f"{type(e).__name__}: {e}"
    finally:
        client.close()
        fleet.close()
    row["wall_s"] = round(time.monotonic() - t0, 1)
    return row


_MATRIX = [
    ("drain-migration", _cell_drain_migration),
    ("kill-failover", _cell_kill_failover),
    ("blackhole-beat", _cell_blackhole_beat),
    ("torn-handoff", _cell_torn_handoff),
    ("shm-vanish", _cell_shm_vanish),
    ("slow-hedge", _cell_slow_hedge),
    ("brownout", _cell_brownout),
]


def _print_scorecard(rows: list) -> None:
    width = max(len(r["name"]) for r in rows) + 2
    print(f"\n{'cell':<{width}}{'result':<11}{'wall':<7}{'ttr_s':<8}"
          f"{'lost':<6}{'re_emit':<9}{'parity':<8}notes")
    for r in rows:
        verdict = "RECOVERED" if r["ok"] else "FAILED"
        ttr = "-" if r["ttr_s"] is None else r["ttr_s"]
        par = "-" if r["parity"] is None else str(r["parity"])
        print(f"{r['name']:<{width}}{verdict:<11}{r['wall_s']:<7}"
              f"{ttr:<8}{r['lost']:<6}{r['re_emitted']:<9}{par:<8}"
              f"{r['notes'] or '-'}")
        if r["error"]:
            print(f"{'':<{width}}  {r['error']}")
    good = sum(r["ok"] for r in rows)
    lost = sum(r["lost"] for r in rows)
    print(f"\nchaos_serve_sweep: {good}/{len(rows)} cells recovered, "
          f"{lost} lost request(s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serving-plane fault-injection sweep "
        "(docs/FAULT_TOLERANCE.md, docs/SERVING.md)."
    )
    ap.add_argument("--selftest", action="store_true",
                    help="fast sweep-machinery self-checks (no fleets)")
    ap.add_argument("--only", default=None,
                    help="run a single matrix cell by name")
    args = ap.parse_args(argv)

    if args.selftest:
        problems = _selftest()
        for p in problems:
            print(f"chaos_serve_sweep selftest: {p}", file=sys.stderr)
        print("chaos_serve_sweep selftest: "
              + ("FAILED" if problems else "OK"))
        return 1 if problems else 0

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    rows = []
    for name, cell in _MATRIX:
        if args.only and name != args.only:
            continue
        print(f"chaos_serve_sweep: running {name} ...", flush=True)
        rows.append(cell())
    if not rows:
        print(f"chaos_serve_sweep: no cell named {args.only!r}",
              file=sys.stderr)
        return 2
    _print_scorecard(rows)
    return 0 if all(r["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
