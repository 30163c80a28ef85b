"""Engine watch: per-query accounting of the TPU engine's silent
performance killers.

The reference merges per-operator RuntimeStatsColl from cop tasks into
EXPLAIN ANALYZE and exports Prometheus collectors per subsystem
(pkg/metrics). For a jit-compiled accelerator engine the equivalent
blind spots are different: XLA (re)compilations, retraces (a plan whose
cache key keeps missing because its input shapes keep changing),
host<->device transfer bytes, and device-memory high-water. "Accelerating
Presto with GPUs" and the pushdown cost analyses (PAPERS.md) both show
these dominate accelerated query latency when unobserved.

Accounting model:
- every counter lands in the global REGISTRY (tidbtpu_engine_*);
- a thread-local *current query record* additionally captures the same
  deltas per statement (opened by the session around each top-level
  statement), and finished records land in a ring buffer surfaced as
  information_schema.TPU_ENGINE;
- ``watched_jit(fn, sig)`` wraps ``jax.jit`` so each actual trace (the
  wrapped python body only runs when XLA compiles) is counted; a second
  trace for the same plan signature is a *retrace* — the recompile
  hunter's needle.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
from typing import Dict, List, Optional

from tidb_tpu.utils import racecheck
from tidb_tpu.utils.metrics import REGISTRY

#: plan signatures whose first compile was already seen; a trace for a
#: member is a retrace. Bounded: reset when it grows past this (the
#: retrace baseline restarts, which only under-counts).
_MAX_SIGS = 8192

#: per-plan-signature XLA cost-analysis cache bound (cost is a
#: property of the lowered program, so one harvest per signature)
_MAX_COSTS = 1024


def extract_cost_keys(ca) -> Dict[str, float]:
    """Normalize one jax ``cost_analysis()`` result to the three
    attributes the engine surfaces: flops, bytes accessed, output
    bytes. KEY-GUARDED: the CPU and TPU backends report different key
    sets (CPU's HLO analysis spells output traffic
    ``bytes accessedout{}``; TPU compiled analyses have shipped
    ``bytes accessed output`` / nothing at all across versions), and a
    missing key must read as absent, not crash the compile path."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out: Dict[str, float] = {}
    for dst, keys in (
        ("flops", ("flops",)),
        ("bytes_accessed", ("bytes accessed",)),
        ("output_bytes", (
            "bytes accessedout{}", "bytes accessed output", "output bytes",
        )),
    ):
        for key in keys:
            v = ca.get(key)
            if isinstance(v, (int, float)) and v == v and v >= 0:
                out[dst] = float(v)
                break
    return out


class QueryEngineRecord:
    """Engine-side resource accounting for one statement."""

    __slots__ = (
        "qid", "query", "jit_compilations", "retraces", "h2d_bytes",
        "d2h_bytes", "device_mem_peak_bytes", "duration_s",
        "compile_flops", "compile_bytes_accessed",
        "compile_output_bytes",
    )

    def __init__(self, qid: int, query: str):
        self.qid = qid
        self.query = query
        self.jit_compilations = 0
        self.retraces = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.device_mem_peak_bytes = 0
        self.duration_s = 0.0
        # XLA cost analysis summed over this statement's compiles
        # (lowered-program attributes, key-guarded per backend)
        self.compile_flops = 0.0
        self.compile_bytes_accessed = 0.0
        self.compile_output_bytes = 0.0


class EngineWatch:
    def __init__(self, capacity: int = 256):
        self._tls = threading.local()
        self._lock = racecheck.make_lock("engine_watch")
        self._seen_sigs = set()
        self._recent = collections.deque(maxlen=capacity)
        self._qid = itertools.count(1)
        #: plan signature -> harvested XLA cost analysis (one lowering
        #: pass per signature; repeated compiles reuse the cached cost)
        self._cost_by_sig: "collections.OrderedDict" = (
            collections.OrderedDict()
        )

    # -- per-query scope (opened by the session per top-level stmt) ----
    def begin_query(self, query: str) -> None:
        self._tls.rec = QueryEngineRecord(next(self._qid), str(query)[:256])

    def end_query(self, elapsed_s: float) -> None:
        rec = getattr(self._tls, "rec", None)
        self._tls.rec = None
        if rec is None:
            return
        rec.duration_s = float(elapsed_s)
        with self._lock:
            self._recent.append(rec)

    def current(self) -> Optional[QueryEngineRecord]:
        return getattr(self._tls, "rec", None)

    # -- notes (called from the engine hot paths; all O(1)) ------------
    def note_trace(self, sig) -> None:
        """One actual jax trace (= one XLA compilation) at a watched
        site; `sig` is the plan signature whose cache key missed."""
        with self._lock:
            if len(self._seen_sigs) > _MAX_SIGS:
                self._seen_sigs.clear()
            retrace = sig in self._seen_sigs
            self._seen_sigs.add(sig)
        REGISTRY.counter(
            "tidbtpu_engine_jit_compilations", "XLA compilations"
        ).inc()
        if retrace:
            REGISTRY.counter(
                "tidbtpu_engine_retraces",
                "recompiles of an already-compiled plan signature "
                "(cache-key misses: shape growth, stale widths)",
            ).inc()
        rec = self.current()
        if rec is not None:
            rec.jit_compilations += 1
            if retrace:
                rec.retraces += 1

    def note_h2d(self, nbytes: int) -> None:
        REGISTRY.counter(
            "tidbtpu_engine_h2d_bytes", "host->device transfer bytes"
        ).inc(nbytes)
        rec = self.current()
        if rec is not None:
            rec.h2d_bytes += int(nbytes)

    def note_d2h(self, nbytes: int) -> None:
        REGISTRY.counter(
            "tidbtpu_engine_d2h_bytes", "device->host transfer bytes"
        ).inc(nbytes)
        rec = self.current()
        if rec is not None:
            rec.d2h_bytes += int(nbytes)

    def d2h_batch(self, batch) -> None:
        """Account a whole fetched device batch (the steady-state
        single fetch in planner/physical.py)."""
        try:
            nb = int(batch.row_valid.nbytes)
            for dc in batch.cols.values():
                nb += int(dc.data.nbytes) + int(dc.valid.nbytes)
        except Exception:
            return
        self.note_d2h(nb)

    def note_device_mem(self, nbytes: int) -> None:
        """Admitted working-set estimate for one launch (scan batches +
        operator tiles) — the per-query device-memory high-water."""
        REGISTRY.gauge(
            "tidbtpu_engine_device_mem_highwater_bytes",
            "largest admitted per-launch device working set",
        ).set_max(nbytes)
        rec = self.current()
        if rec is not None:
            rec.device_mem_peak_bytes = max(
                rec.device_mem_peak_bytes, int(nbytes)
            )

    # -- XLA compile cost analysis (per plan signature) ----------------
    def cost_for_sig(self, sig) -> Optional[Dict[str, float]]:
        """The cached cost analysis for one plan signature, or None if
        never harvested (the compile either predates the watch or the
        backend declined to analyze)."""
        with self._lock:
            c = self._cost_by_sig.get(sig)
            return dict(c) if c else None

    def note_compile_cost(
        self, sig, cost: Dict[str, float], wall_s: float = 0.0
    ) -> None:
        """One compile's harvested cost analysis: cached per signature,
        summed onto the current statement's record, counted on the
        registry, and stamped as a timeline compile event when a
        capture is live (the EVENT window is the trace wall that just
        finished)."""
        cost = {k: float(v) for k, v in (cost or {}).items()}
        with self._lock:
            if cost:
                if len(self._cost_by_sig) >= _MAX_COSTS:
                    self._cost_by_sig.popitem(last=False)
                self._cost_by_sig[sig] = dict(cost)
        if cost.get("flops"):
            REGISTRY.counter(
                "tidbtpu_engine_compile_flops_total",
                "XLA cost-analysis flops summed over compiles",
            ).inc(cost["flops"])
        if cost.get("bytes_accessed"):
            REGISTRY.counter(
                "tidbtpu_engine_compile_bytes_accessed_total",
                "XLA cost-analysis bytes-accessed summed over compiles",
            ).inc(cost["bytes_accessed"])
        rec = self.current()
        if rec is not None and cost:
            rec.compile_flops += cost.get("flops", 0.0)
            rec.compile_bytes_accessed += cost.get("bytes_accessed", 0.0)
            rec.compile_output_bytes += cost.get("output_bytes", 0.0)
        from tidb_tpu.obs.timeline import TIMELINE
        import time as _time

        TIMELINE.emit_event(
            "compile", _sig_label(sig), _time.time() - max(wall_s, 0.0),
            wall_s, track="compiles",
            args={"cost_analysis": cost} if cost else None,
        )

    def current_compile_cost(self) -> Dict[str, float]:
        """The CURRENT statement's summed compile cost so far (empty
        when no record is open or nothing compiled) — the EXPLAIN
        ANALYZE compile row and the worker reply's piggybacked
        per-fragment cost read from here."""
        rec = self.current()
        if rec is None:
            return {}
        out = {}
        if rec.compile_flops:
            out["flops"] = rec.compile_flops
        if rec.compile_bytes_accessed:
            out["bytes_accessed"] = rec.compile_bytes_accessed
        if rec.compile_output_bytes:
            out["output_bytes"] = rec.compile_output_bytes
        if out:
            out["compiles"] = float(rec.jit_compilations)
        return out

    def current_peak_bytes(self) -> int:
        """The CURRENT statement's device-mem high-water so far (0
        when no record is open) — the serving tier's working-set
        feedback: session routing hands it to
        AdmissionController.release() so the next admission of the
        same plan fingerprint gates on what the shape really used
        (coordinator-side working set; worker slices size the same
        plan smaller, so the estimate is conservative)."""
        rec = self.current()
        return int(rec.device_mem_peak_bytes) if rec is not None else 0

    # -- surfaces ------------------------------------------------------
    def rows(self) -> List[tuple]:
        """information_schema.TPU_ENGINE rows, oldest first (the
        compile cost-analysis columns append at the end so positional
        consumers of the pre-existing 8-tuple keep working)."""
        with self._lock:
            recs = list(self._recent)
        return [
            (
                r.qid, r.query, r.jit_compilations, r.retraces,
                r.h2d_bytes, r.d2h_bytes, r.device_mem_peak_bytes,
                r.duration_s, r.compile_flops, r.compile_bytes_accessed,
                r.compile_output_bytes,
            )
            for r in recs
        ]


ENGINE_WATCH = EngineWatch()


def _sig_label(sig) -> str:
    """Short human label for a plan signature (timeline event names)."""
    try:
        if isinstance(sig, tuple) and sig and isinstance(sig[0], str):
            return f"{sig[0]}:{'%08x' % (hash(sig) & 0xFFFFFFFF)}"
        return "%08x" % (hash(sig) & 0xFFFFFFFF)
    except TypeError:
        return "jit"


#: thread-local flags coordinating the wrapper, the traced body and
#: the cost-analysis harvest lower (which re-runs the traced body and
#: must not double-count the compile)
_TLS = threading.local()

#: cost-analysis harvest switch. The harvest costs one extra python
#: trace per DISTINCT plan signature (~tens of ms on engine-sized
#: programs — jax re-lowers; XLA does not recompile), so it is not
#: free on compile-heavy suites: it runs when a fleet timeline capture
#: is live (obs/timeline.py — compile events must carry their cost
#: attributes), when TIDB_TPU_COST_ANALYSIS=1, or after
#: set_cost_analysis(True). Cached signatures are reused either way.
_COST_ALWAYS = os.environ.get("TIDB_TPU_COST_ANALYSIS", "") == "1"


def set_cost_analysis(enabled: bool) -> None:
    global _COST_ALWAYS
    _COST_ALWAYS = bool(enabled)


def set_cost_wanted(flag: bool) -> None:
    """Thread-scoped harvest opt-in: a worker process has no live
    TIMELINE capture of its own, so a timeline-captured dispatch asks
    for cost analysis per task (server/engine_rpc.py sets this around
    the execute window — compiles run on the handler thread)."""
    _TLS.cost_wanted = bool(flag)


def cost_analysis_enabled() -> bool:
    if _COST_ALWAYS or getattr(_TLS, "cost_wanted", False):
        return True
    from tidb_tpu.obs.timeline import TIMELINE

    return TIMELINE.active()


def _harvest_cost(jitted, args, kwargs) -> Dict[str, float]:
    """Best-effort ``Lowered.cost_analysis()`` for the shapes just
    compiled. The lowering pass re-traces the python body (accounting
    suppressed via the thread-local) but does NOT re-run XLA — on jax
    0.4.x the analysis comes from the lowered HLO. Any failure returns
    {}: cost analysis is telemetry, never a correctness dependency."""
    _TLS.cost_capture = True
    try:
        return extract_cost_keys(
            jitted.lower(*args, **kwargs).cost_analysis()
        )
    except Exception:
        return {}
    finally:
        _TLS.cost_capture = False


def watched_jit(fn, sig=None, **jit_kwargs):
    """``jax.jit`` with compile accounting: the wrapped python body runs
    only when jax actually (re)traces, so each execution of the wrapper
    is one XLA compilation charged to `sig` (default: the function's
    identity). The trace wall additionally lands in the flight
    recorder's ``compile`` phase — tracing runs synchronously on the
    statement's thread, so the charge hits the right query — and each
    FRESH trace harvests the lowered program's XLA cost analysis
    (flops / bytes accessed / output bytes), cached per signature and
    surfaced through information_schema.TPU_ENGINE, statements_summary
    and timeline compile events. Returns a plain callable (every call
    site is call-only; the jit object stays an implementation detail).
    """
    import jax

    from tidb_tpu.obs.flight import FLIGHT

    watch_sig = sig if sig is not None else ("fn", id(fn))

    def traced(*a, **k):
        if getattr(_TLS, "cost_capture", False):
            # the harvest lower re-traces: not a new compile
            return fn(*a, **k)
        _TLS.fresh_trace = True
        ENGINE_WATCH.note_trace(watch_sig)
        # tracing runs synchronously on the statement's thread: the
        # span charges the compile phase and marks it for the Top SQL
        # sampler, restoring the enclosing phase on exit
        span = FLIGHT.span("compile")
        try:
            with span:
                return fn(*a, **k)
        finally:
            # the SAME wall the flight recorder's compile phase
            # charges — the timeline compile event must not absorb
            # the first call's device execution (wrapper reads it)
            _TLS.trace_wall = span.seconds

    # XLA names the module after the traced function: the kind of the
    # signature (steady, discover, stream-*) tells a statement's program
    # from ANALYZE's kernels on the device trace. The name is part of
    # the persistent compile cache's key.
    if isinstance(sig, tuple) and sig and isinstance(sig[0], str):
        traced.__name__ = traced.__qualname__ = sig[0].replace("-", "_")

    jitted = jax.jit(traced, **jit_kwargs)

    def wrapper(*a, **k):
        _TLS.fresh_trace = False
        out = jitted(*a, **k)
        if getattr(_TLS, "fresh_trace", False):
            # one harvest per signature: a retrace of a known plan
            # reuses the cached analysis instead of re-lowering, and
            # the harvest itself runs only when someone is looking
            # (live timeline capture / explicit enable)
            cost = ENGINE_WATCH.cost_for_sig(watch_sig)
            if cost is None and cost_analysis_enabled():
                cost = _harvest_cost(jitted, a, k)
            ENGINE_WATCH.note_compile_cost(
                watch_sig, cost or {},
                wall_s=getattr(_TLS, "trace_wall", 0.0),
            )
        return out

    return wrapper
