"""Program ledger: the compiled-executable observatory.

Every subsystem in this tree pins "zero steady-state recompiles" via
``compile_event_count()`` deltas, but the counter only *counts* — when
a pin fires nobody learns which call site recompiled or why, and the
XLA compiler's own accounting (``cost_analysis()`` FLOPs and
bytes-accessed, ``memory_analysis()`` argument/output/temp bytes) is
thrown away.  This module closes both gaps with one wrapper:

:func:`ledgered_jit` replaces a ``jax.jit(fn, ...)`` call site.  The
returned :class:`LedgeredFunction` owns dispatch through the
ahead-of-time ``Lowered.compile()`` executable, so at first dispatch it
captures — without a second compile —

* the abstract argument **signature**: per-leaf shapes/dtypes, the
  pytree structure fingerprint, static values, and donation;
* the **compile wall time** (measured directly around ``lower()`` +
  ``compile()``);
* the lowered executable's ``cost_analysis()`` (FLOPs, bytes accessed)
  and ``memory_analysis()`` (argument/output/temp/generated-code
  bytes) — the inputs for roofline MFU and HBM sizing.

When a dispatch misses every compiled variant of its site, the new
signature is diffed against the last one and a schema-valid
``recompile`` record is emitted (``telemetry/schema.py:
validate_recompile_record``) that **names the offending argument and
what changed** — shape vs dtype vs structure vs donation — so every
zero-recompile pin in tests and benches prints an attribution when it
fires instead of a bare count.

Dispatch discipline (why this is safe on hot paths):

* **Fast path** is one attribute load and a direct ``Compiled`` call
  inside ``try/except`` — no per-call fingerprinting.  A signature
  mismatch surfaces as the executable's own ``TypeError``/
  ``ValueError``, which routes to the slow path.  Its overhead over
  a bare jit call has not been measured on the chip.
* AOT compiles do NOT populate the normal jit call cache, so the
  wrapper never falls back to the plain jitted callable for concrete
  arguments — that would silently double every compile.  The one
  exception is **tracer** inputs (a ledgered program invoked inside an
  enclosing trace), where the plain jit inlines correctly.
* ``RLT_PROGRAM_LEDGER=0`` is the kill switch: :func:`ledgered_jit`
  degrades to a bare ``jax.jit`` (the A/B baseline).

The module imports jax lazily: schema gates and the flight recorder
read :func:`snapshot` from jax-free processes.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple,
)

from ray_lightning_tpu.telemetry.spans import phase

__all__ = [
    "ArgSig",
    "LedgeredFunction",
    "ProgramLedger",
    "ProgramRecord",
    "Signature",
    "diff_signatures",
    "hbm_report",
    "hlo_text",
    "ledger",
    "ledgered_jit",
    "recompile_records",
    "roofline",
    "snapshot",
]

_LOG = logging.getLogger("ray_lightning_tpu.program_ledger")

#: Ring caps: an observatory must never become the leak it watches.
_MAX_RECORDS = 512
_MAX_RECOMPILES = 128

#: site -> the live LedgeredFunction most recently built for it (latest
#: wins; weak values so the registry never pins a retraced function — or
#: its compiled executables — alive).  Feeds :func:`hlo_text`.
import weakref  # noqa: E402 - grouped with its sole consumer

_SITE_FUNCTIONS: "weakref.WeakValueDictionary[str, Any]" = (
    weakref.WeakValueDictionary()
)


# ---------------------------------------------------------------------------
# Signatures — the per-dispatch abstract fingerprint
# ---------------------------------------------------------------------------

class ArgSig(NamedTuple):
    """One top-level argument's abstract shape: its pytree structure
    string plus per-leaf ``(path, shape, dtype)`` rows."""

    name: str
    treedef: str
    leaves: Tuple[Tuple[str, Tuple[int, ...], str], ...]


class Signature(NamedTuple):
    """The full call-site fingerprint a variant is keyed on."""

    args: Tuple[ArgSig, ...]
    statics: Tuple[Tuple[str, str], ...]   # (name, repr(value))
    donate: Tuple[int, ...]


def _leaf_sig(leaf: Any) -> Tuple[Tuple[int, ...], str]:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return tuple(int(d) for d in shape), str(dtype)
    # Python scalars: weak-typed operands — the *type* is the dtype
    # identity (2 vs 3 share an executable; 2 vs 2.0 do not).
    return (), type(leaf).__name__


_DTYPE_SHORT = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float64": "f64", "int32": "i32", "int64": "i64", "int8": "i8",
    "uint32": "u32", "uint8": "u8", "bool": "b1",
}


def _fmt_leaf(shape: Tuple[int, ...], dtype: str) -> str:
    d = _DTYPE_SHORT.get(dtype, dtype)
    return f"{d}[{','.join(str(s) for s in shape)}]"


def _fmt_sig(sig: Signature) -> str:
    """Compact human-readable signature for ledger rows."""
    parts = []
    for a in sig.args:
        if len(a.leaves) <= 3:
            body = ",".join(_fmt_leaf(s, d) for _, s, d in a.leaves)
        else:
            body = f"<{len(a.leaves)} leaves>"
        parts.append(f"{a.name}:{body}")
    for name, val in sig.statics:
        parts.append(f"{name}={val}")
    out = "|".join(parts)
    if sig.donate:
        out += f"|donate={tuple(sig.donate)}"
    return out


def _clip(s: str, n: int = 160) -> str:
    return s if len(s) <= n else s[: n - 3] + "..."


def diff_signatures(old: Signature, new: Signature) -> Dict[str, Any]:
    """Attribution for a signature change: which argument, what kind of
    delta (``shape`` / ``dtype`` / ``structure`` / ``donation`` /
    ``static``), and the before/after rendering.  Pure — the negative
    schema self-tests drive it without jax."""
    if tuple(old.donate) != tuple(new.donate):
        return {"kind": "donation", "argument": "donate_argnums",
                "old": str(tuple(old.donate)),
                "new": str(tuple(new.donate))}
    if old.statics != new.statics:
        o, n = dict(old.statics), dict(new.statics)
        for name in list(n) + [k for k in o if k not in n]:
            if o.get(name) != n.get(name):
                return {"kind": "static", "argument": name,
                        "old": str(o.get(name)), "new": str(n.get(name))}
    if [a.name for a in old.args] != [a.name for a in new.args]:
        return {"kind": "structure", "argument": "<arity>",
                "old": f"{len(old.args)} args: "
                       f"{[a.name for a in old.args]}",
                "new": f"{len(new.args)} args: "
                       f"{[a.name for a in new.args]}"}
    for oa, na in zip(old.args, new.args):
        if oa.treedef != na.treedef:
            return {"kind": "structure", "argument": na.name,
                    "old": _clip(oa.treedef), "new": _clip(na.treedef)}
        for ol, nl in zip(oa.leaves, na.leaves):
            arg = na.name + (nl[0] or "")
            if ol[1] != nl[1]:
                return {"kind": "shape", "argument": arg,
                        "old": _fmt_leaf(ol[1], ol[2]),
                        "new": _fmt_leaf(nl[1], nl[2])}
            if ol[2] != nl[2]:
                return {"kind": "dtype", "argument": arg,
                        "old": ol[2], "new": nl[2]}
    return {"kind": "structure", "argument": "<unattributed>",
            "old": "", "new": ""}


# ---------------------------------------------------------------------------
# Records
# ---------------------------------------------------------------------------

@dataclass
class ProgramRecord:
    """One compiled executable: identity, cost, and memory accounting."""

    site: str
    variant: int
    signature: str
    compile_s: float
    backend: str = ""
    donated: Tuple[int, ...] = ()
    ncalls: int = 0
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None

    def row(self) -> Dict[str, Any]:
        """Schema row (``validate_program_row``): required identity
        keys always present, accounting keys only when the backend
        produced them."""
        out: Dict[str, Any] = {
            "site": self.site,
            "variant": self.variant,
            "ncalls": int(self.ncalls),
            "compile_s": float(self.compile_s),
            "signature": self.signature,
        }
        if self.backend:
            out["backend"] = self.backend
        if self.donated:
            out["donated"] = str(tuple(self.donated))
        for key in ("flops", "bytes_accessed"):
            val = getattr(self, key)
            if val is not None:
                out[key] = float(val)
        for key in ("argument_bytes", "output_bytes", "temp_bytes",
                    "alias_bytes", "generated_code_bytes"):
            val = getattr(self, key)
            if val is not None:
                out[key] = int(val)
        return out


def _cost_dict(compiled: Any) -> Dict[str, float]:
    """``cost_analysis()`` normalised: this jax returns a single-element
    list of dicts; newer ones return the dict.  Absent/failed analysis
    degrades to empty — accounting is best-effort by contract."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend-dependent, never fatal
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if cost else {}


# ---------------------------------------------------------------------------
# The process-wide ledger
# ---------------------------------------------------------------------------

class ProgramLedger:
    """Registry of every executable dispatched through a
    :class:`LedgeredFunction`, plus the recompile-forensics ring."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: List[ProgramRecord] = []   # guarded by self._lock
        self._recompiles: List[Dict[str, Any]] = []  # guarded by self._lock
        self._site_last: Dict[str, Signature] = {}   # guarded by self._lock
        self._dropped = 0                         # guarded by self._lock
        self._emitters: List[Callable[[Dict[str, Any]], None]] = []

    # -- recording (called from LedgeredFunction under its own lock) ---------
    def record_program(self, record: ProgramRecord,
                       sig: Signature) -> None:
        with self._lock:
            if len(self._records) < _MAX_RECORDS:
                self._records.append(record)
            else:
                self._dropped += 1
            self._site_last[record.site] = sig

    def last_signature(self, site: str) -> Optional[Signature]:
        with self._lock:
            return self._site_last.get(site)

    def record_recompile(self, site: str, attribution: Dict[str, Any],
                         variant: int) -> Dict[str, Any]:
        """Build, store, log, and fan out one recompile record."""
        event = {
            "type": "recompile",
            "site": site,
            "kind": attribution["kind"],
            "argument": attribution["argument"],
            "old": attribution.get("old", ""),
            "new": attribution.get("new", ""),
            "variant": int(variant),
            "ts": time.time(),
        }
        with self._lock:
            self._recompiles.append(event)
            if len(self._recompiles) > _MAX_RECOMPILES:
                del self._recompiles[0]
            emitters = list(self._emitters)
        # The attribution must be adjacent to any zero-recompile pin
        # that fires: warn unconditionally, not at debug level.
        _LOG.warning(
            "recompile at %s (variant %d): %s change on %r: %s -> %s",
            site, variant, event["kind"], event["argument"],
            event["old"], event["new"],
        )
        for emit in emitters:
            try:
                emit(dict(event))
            except Exception:  # noqa: BLE001 - observers never break dispatch
                _LOG.debug("recompile emitter failed", exc_info=True)
        return event

    def add_emitter(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        """Fan recompile records out to a live channel (the monitor's
        event stream, a test capture list)."""
        with self._lock:
            self._emitters.append(fn)

    def remove_emitter(self, fn: Callable[[Dict[str, Any]], None]) -> None:
        with self._lock:
            try:
                self._emitters.remove(fn)
            except ValueError:
                pass

    # -- reading -------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Picklable observatory state (schema:
        ``validate_program_snapshot``)."""
        with self._lock:
            rows = [r.row() for r in self._records]
            recompiles = [dict(e) for e in self._recompiles]
            dropped = self._dropped
        out: Dict[str, Any] = {
            "programs": rows,
            "recompiles": recompiles,
            "compile_time_total_s": round(
                sum(r["compile_s"] for r in rows), 6
            ),
        }
        if dropped:
            out["dropped"] = dropped
        return out

    def compile_time_total_s(self) -> float:
        with self._lock:
            return sum(r.compile_s for r in self._records)

    def sites(self) -> List[str]:
        with self._lock:
            seen: Dict[str, None] = {}
            for r in self._records:
                seen.setdefault(r.site, None)
            return list(seen)

    def site_flops(self, site: str) -> Optional[float]:
        """FLOPs of the most-called variant at ``site`` (prefix match
        when no exact site exists) — the measured side of the MFU
        drift guard."""
        with self._lock:
            exact = [r for r in self._records if r.site == site]
            rows = exact or [
                r for r in self._records if r.site.startswith(site)
            ]
            rows = [r for r in rows if r.flops is not None]
            if not rows:
                return None
            return float(max(rows, key=lambda r: r.ncalls).flops)

    def site_flops_latest(self, site: str) -> Optional[float]:
        """FLOPs of the most recently compiled variant at ``site``.
        The train loop reads this at step-0 compile time, when the
        latest record IS the program that just compiled; the
        most-called view above would leak a previous fit's program in
        a long-lived process (sequential fits in one pytest run
        register many train/step variants)."""
        with self._lock:
            for r in reversed(self._records):
                if r.site == site and r.flops is not None:
                    return float(r.flops)
        return None

    def reset(self) -> None:
        """Test/bench isolation: drop all records and rings.  Live
        LedgeredFunctions keep their compiled variants (no recompile
        storm) — only the observatory state clears."""
        with self._lock:
            self._records.clear()
            self._recompiles.clear()
            self._site_last.clear()
            self._dropped = 0


_GLOBAL = ProgramLedger()


def ledger() -> ProgramLedger:
    """The process-wide ledger singleton."""
    return _GLOBAL


def snapshot() -> Dict[str, Any]:
    return _GLOBAL.snapshot()


def recompile_records() -> List[Dict[str, Any]]:
    return list(_GLOBAL.snapshot()["recompiles"])


# ---------------------------------------------------------------------------
# The dispatch wrapper
# ---------------------------------------------------------------------------

class _Variant:
    __slots__ = ("sig", "compiled", "statics", "record")

    def __init__(self, sig: Signature, compiled: Any,
                 statics: Tuple[Any, ...], record: ProgramRecord):
        self.sig = sig
        self.compiled = compiled
        self.statics = statics
        self.record = record


class LedgeredFunction:
    """A jit call site that owns dispatch through its AOT-compiled
    executables and reports every compile to the ledger.

    Dispatch: the most-recently-used ``Compiled`` is tried directly
    (its own argument check is the fast-path guard); a mismatch falls
    to the slow path, which fingerprints, reuses a matching variant, or
    lowers+compiles a new one and emits the recompile attribution.
    """

    def __init__(self, fn: Callable, site: str,
                 registry: Optional[ProgramLedger] = None,
                 arg_names: Optional[Sequence[str]] = None,
                 name_of: Optional[Callable[..., str]] = None,
                 **jit_kwargs: Any):
        import jax

        self._fn = fn
        self.site = site
        # ``name_of(*args)``: the name a variant's program is lowered
        # under (``jit_<name>`` on a device trace), for a site whose
        # variants are worth telling apart there; else all carry
        # ``fn``'s.  One jit a name, made when the name is first seen.
        self._name_of = name_of
        self._jit_kwargs = jit_kwargs
        self._named: Dict[str, Any] = {}
        self._ledger = registry if registry is not None else _GLOBAL
        donate = jit_kwargs.get("donate_argnums", ())
        if isinstance(donate, int):
            donate = (donate,)
        self._donate: Tuple[int, ...] = tuple(donate)
        static = jit_kwargs.get("static_argnums", ())
        if isinstance(static, int):
            static = (static,)
        self._static: Tuple[int, ...] = tuple(static)
        self._jit = jax.jit(fn, **jit_kwargs)
        if arg_names is None:
            arg_names = _infer_arg_names(fn)
        self._arg_names: Tuple[str, ...] = tuple(arg_names or ())
        self._variants: List[_Variant] = []   # guarded by self._lock
        self._mru: Optional[_Variant] = None
        self._lock = threading.Lock()
        _SITE_FUNCTIONS[site] = self

    # -- introspection (tests, tooling) --------------------------------------
    @property
    def variants(self) -> int:
        with self._lock:
            return len(self._variants)

    def lower(self, *args: Any, **kwargs: Any):
        """Pass through to the underlying jit's ``lower`` (warm-compile
        paths use it)."""
        return self._jit_for(args).lower(*args, **kwargs)

    def _jit_for(self, args: Tuple[Any, ...]):
        """The jit that lowers these arguments: the site's, or with
        ``name_of`` the one of the name it gives them."""
        if self._name_of is None:
            return self._jit
        name = self._name_of(*args)
        jit = self._named.get(name)
        if jit is None:
            import jax

            fn = self._fn

            @functools.wraps(fn)
            def named(*a: Any, **kw: Any):
                return fn(*a, **kw)

            named.__name__ = named.__qualname__ = name
            jit = self._named[name] = jax.jit(named, **self._jit_kwargs)
        return jit

    # -- dispatch ------------------------------------------------------------
    def __call__(self, *args: Any, **kwargs: Any):
        mru = self._mru
        if mru is not None and (
            not self._static or self._statics_of(args) == mru.statics
        ):
            try:
                out = mru.compiled(*self._dynamic(args), **kwargs)
            except (TypeError, ValueError):
                # Signature/sharding miss (or a tracer input): the slow
                # path re-resolves and re-raises genuine errors.
                pass
            else:
                mru.record.ncalls += 1
                return out
        return self._dispatch_slow(args, kwargs)

    def _statics_of(self, args: Tuple[Any, ...]) -> Tuple[Any, ...]:
        return tuple(args[i] for i in self._static if i < len(args))

    def _dynamic(self, args: Tuple[Any, ...]) -> Tuple[Any, ...]:
        if not self._static:
            return args
        return tuple(
            a for i, a in enumerate(args) if i not in self._static
        )

    def _dispatch_slow(self, args: Tuple[Any, ...],
                       kwargs: Dict[str, Any]):
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
            # Invoked inside an enclosing trace: a Compiled cannot take
            # tracers; the plain jit inlines correctly and adds no
            # executable of its own.
            return self._jit(*args, **kwargs)
        sig = self._signature(args, kwargs)
        with self._lock:
            variant = next(
                (v for v in self._variants if v.sig == sig), None
            )
            if variant is None:
                variant = self._compile_locked(sig, args, kwargs)
            self._mru = variant
        out = variant.compiled(*self._dynamic(args), **kwargs)
        variant.record.ncalls += 1
        return out

    def _signature(self, args: Tuple[Any, ...],
                   kwargs: Dict[str, Any]) -> Signature:
        import jax

        arg_sigs: List[ArgSig] = []
        statics: List[Tuple[str, str]] = []
        for i, a in enumerate(args):
            name = (self._arg_names[i] if i < len(self._arg_names)
                    else f"arg{i}")
            if i in self._static:
                statics.append((name, repr(a)))
                continue
            leaves, treedef = jax.tree_util.tree_flatten_with_path(a)
            arg_sigs.append(ArgSig(name, str(treedef), tuple(
                (jax.tree_util.keystr(path),) + _leaf_sig(leaf)
                for path, leaf in leaves
            )))
        for key in sorted(kwargs):
            leaves, treedef = jax.tree_util.tree_flatten_with_path(
                kwargs[key]
            )
            arg_sigs.append(ArgSig(key, str(treedef), tuple(
                (jax.tree_util.keystr(path),) + _leaf_sig(leaf)
                for path, leaf in leaves
            )))
        return Signature(tuple(arg_sigs), tuple(statics), self._donate)

    # rlt: holds self._lock
    def _compile_locked(self, sig: Signature, args: Tuple[Any, ...],
                        kwargs: Dict[str, Any]) -> _Variant:
        import jax

        baseline = (self._mru.sig if self._mru is not None
                    else self._ledger.last_signature(self.site))
        # A compile inside a profiled window is a named span on the
        # trace's clock (``rlt:compile``, ``site=``), not only a count.
        with phase("compile", site=self.site) as ph:
            compiled = self._jit_for(args).lower(*args, **kwargs).compile()
        compile_s = ph.dur
        cost = _cost_dict(compiled)
        record = ProgramRecord(
            site=self.site,
            variant=len(self._variants),
            signature=_fmt_sig(sig),
            compile_s=compile_s,
            backend=jax.default_backend(),
            donated=self._donate,
            flops=cost.get("flops"),
            bytes_accessed=cost.get("bytes accessed"),
        )
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 - backend-dependent
            mem = None
        if mem is not None:
            record.argument_bytes = getattr(
                mem, "argument_size_in_bytes", None)
            record.output_bytes = getattr(
                mem, "output_size_in_bytes", None)
            record.temp_bytes = getattr(mem, "temp_size_in_bytes", None)
            record.alias_bytes = getattr(
                mem, "alias_size_in_bytes", None)
            record.generated_code_bytes = getattr(
                mem, "generated_code_size_in_bytes", None)
        if baseline is not None and baseline != sig:
            self._ledger.record_recompile(
                self.site, diff_signatures(baseline, sig),
                variant=len(self._variants),
            )
        variant = _Variant(sig, compiled, self._statics_of(args), record)
        self._variants.append(variant)
        self._ledger.record_program(record, sig)
        return variant


def _infer_arg_names(fn: Callable) -> Tuple[str, ...]:
    import inspect

    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return ()
    names: List[str] = []
    for p in params.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            names.append(p.name)
        else:
            break
    return tuple(names)


def _enabled() -> bool:
    return os.environ.get("RLT_PROGRAM_LEDGER", "1") not in ("0", "off")


def ledgered_jit(fn: Callable, *, site: str,
                 arg_names: Optional[Sequence[str]] = None,
                 name_of: Optional[Callable[..., str]] = None,
                 **jit_kwargs: Any) -> Callable:
    """Drop-in for ``jax.jit(fn, **jit_kwargs)`` that registers the
    call site with the process ledger.  ``site`` names the program in
    every surface (snapshot rows, recompile attributions,
    ``rlt_program_*`` metrics, the rlt_top pane).  ``name_of(*args)``
    names each variant's lowered module (the engine's prefill: a name a
    bucket), so that a device trace tells the variants of one site
    apart; the site stays one.

    ``RLT_PROGRAM_LEDGER=0`` disables the observatory entirely and
    returns a bare ``jax.jit`` — the overhead-A/B baseline (every
    variant then carries ``fn``'s name)."""
    if not _enabled():
        import jax

        return jax.jit(fn, **jit_kwargs)
    return LedgeredFunction(fn, site, arg_names=arg_names, name_of=name_of,
                            **jit_kwargs)


def hlo_text(site: str) -> Optional[str]:
    """Optimized HLO of the named site's most-recently-used compiled
    variant, or ``None`` when unavailable (ledger disabled, site never
    dispatched, backend without ``as_text``).  Best-effort by design —
    callers gate structural assertions (the comm/compute-overlap bench
    proof) on a non-``None`` return, they do not branch behavior."""
    fn = _SITE_FUNCTIONS.get(site)
    if fn is None:
        return None
    with fn._lock:
        variant = fn._mru or (fn._variants[-1] if fn._variants else None)
    if variant is None:
        return None
    try:
        text = variant.compiled.as_text()
    except Exception:  # noqa: BLE001 - backend-dependent surface
        return None
    return text if isinstance(text, str) else None


# ---------------------------------------------------------------------------
# Derived reports: HBM budget + roofline
# ---------------------------------------------------------------------------

def _best_rows(snap: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Most-called variant per site."""
    best: Dict[str, Dict[str, Any]] = {}
    for row in snap.get("programs", ()):
        cur = best.get(row["site"])
        if cur is None or row["ncalls"] > cur["ncalls"]:
            best[row["site"]] = row
    return best


def hbm_report(snap: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Per-site HBM accounting from ``memory_analysis()``: argument
    bytes (resident operands — params/opt-state for train, the KV pool
    for decode), output bytes, and temp bytes (XLA scratch).  Sites
    report their most-called variant; the peaks are the sizing oracle
    (programs run one at a time per device, so temp is a max, not a
    sum; arguments alias across programs, so that is a max too)."""
    snap = snap if snap is not None else _GLOBAL.snapshot()
    sites: Dict[str, Dict[str, int]] = {}
    for site, row in _best_rows(snap).items():
        entry = {
            key: int(row[key])
            for key in ("argument_bytes", "output_bytes", "temp_bytes")
            if row.get(key) is not None
        }
        if entry:
            sites[site] = entry
    out: Dict[str, Any] = {"sites": sites}
    if sites:
        out["peak_argument_bytes"] = max(
            e.get("argument_bytes", 0) for e in sites.values()
        )
        out["peak_temp_bytes"] = max(
            e.get("temp_bytes", 0) for e in sites.values()
        )
    gen = [
        row.get("generated_code_bytes")
        for row in snap.get("programs", ())
        if row.get("generated_code_bytes") is not None
    ]
    if gen:
        out["generated_code_bytes"] = int(sum(gen))
    return out


def roofline(site: str, peak_flops: Optional[float] = None,
             peak_bytes_per_s: Optional[float] = None,
             snap: Optional[Dict[str, Any]] = None
             ) -> Optional[Dict[str, Any]]:
    """Roofline placement of one program: arithmetic intensity from the
    measured FLOPs / bytes-accessed, and — when the chip peaks are
    supplied — the ridge point and whether the program sits
    compute-bound or memory-bound."""
    snap = snap if snap is not None else _GLOBAL.snapshot()
    rows = [
        r for r in _best_rows(snap).values()
        if (r["site"] == site or r["site"].startswith(site))
        and r.get("flops") is not None
    ]
    if not rows:
        return None
    row = max(rows, key=lambda r: r["ncalls"])
    out: Dict[str, Any] = {"site": row["site"],
                           "flops": float(row["flops"])}
    bytes_accessed = row.get("bytes_accessed")
    if bytes_accessed:
        out["bytes_accessed"] = float(bytes_accessed)
        out["arithmetic_intensity"] = float(row["flops"]) / float(
            bytes_accessed
        )
    if peak_flops and peak_bytes_per_s and bytes_accessed:
        ridge = peak_flops / peak_bytes_per_s
        out["ridge_intensity"] = ridge
        out["bound"] = (
            "compute" if out["arithmetic_intensity"] >= ridge
            else "memory"
        )
    return out
