"""Span tracing through the query path.

Reference: pkg/util/tracing/util.go:21 (opentracing spans opened at
session.ExecuteStmt, Compiler.Compile, distsql.Select, rendered by
TRACE SELECT, pkg/executor/trace.go). Here: a per-session Tracer records
(name, start, duration, depth); the session opens spans around parse /
plan / execute / materialize, and `TRACE <select>` returns them as rows.

Since PR 27 the session's boundaries are timed once, by
``FLIGHT.span`` (obs/flight.py), which feeds the session's Tracer
through ``add`` while ``TRACE`` has it enabled; ``span`` stays for the
fleet's worker-side tracers (server/engine_rpc.py, parallel/shuffle.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional


@dataclasses.dataclass
class Span:
    name: str
    start_s: float
    dur_s: float
    depth: int


class Tracer:
    def __init__(self) -> None:
        self.spans: List[Span] = []
        self._depth = 0
        self._t0: Optional[float] = None
        #: wall-clock time of the last reset(): the cross-process span
        #: anchor — a remote worker ships its own wall_t0 and the
        #: coordinator rebases via the handshake-sampled clock offset
        #: (parallel/dcn.py _merge_remote_spans)
        self.wall_t0: Optional[float] = None
        self.enabled = False

    def reset(self) -> None:
        self.spans = []
        self._depth = 0
        self._t0 = time.perf_counter()
        self.wall_t0 = time.time()

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        if self._t0 is None:
            self.reset()
        start = time.perf_counter()
        self._depth += 1
        depth = self._depth
        try:
            yield
        finally:
            self._depth -= 1
            self.spans.append(
                Span(name, start - self._t0, time.perf_counter() - start, depth)
            )

    def add(self, name: str, start: float, dur_s: float, depth: int) -> None:
        """One closed span timed elsewhere (obs/flight.py FLIGHT.span):
        ``start`` is on ``perf_counter``, like the zero ``reset`` took."""
        self.spans.append(Span(name, start - self._t0, dur_s, max(depth, 1)))

    def add_remote(
        self, spans, label: str, base_s: float = 0.0,
        base_depth: int = 1,
    ) -> None:
        """Merge spans shipped back from a remote worker (the DCN
        fragment reply's span list), host-labeled so the coordinator's
        trace shows where each fragment ran. Accepts Span objects or
        (name, start_s, dur_s, depth) sequences. Remote start offsets
        are relative to the worker's own clock; `base_s` rebases them
        onto this tracer's timeline (the caller knows when the reply
        landed) so rows()'s start-sorted output doesn't put every
        remote span at time zero.

        Depths rebase the same way clocks do: a worker's spans carry
        depths relative to the WORKER's own nesting (a handler that
        opened spans inside other spans ships depths 2, 3, ...), and
        blindly clamping each to >= 1 kept absolute worker depths —
        the coordinator's TRACE output then indented remote spans
        under unrelated neighbouring rows (phantom parents) while a
        worker whose spans all clamped together FLATTENED real
        nesting. Instead the span list's minimum depth maps to
        ``base_depth`` and every other span keeps its RELATIVE depth
        under the host label, so a 2-level worker span renders as two
        nested rows wherever it lands in the merged trace."""
        rel = []
        for s in spans:
            if isinstance(s, Span):
                name, start_s, dur_s, depth = (
                    s.name, s.start_s, s.dur_s, s.depth
                )
            else:
                name, start_s, dur_s, depth = s
            rel.append((name, float(start_s), float(dur_s), int(depth)))
        if not rel:
            return
        dmin = min(d for _n, _s, _d, d in rel)
        base_depth = max(int(base_depth), 1)
        for name, start_s, dur_s, depth in rel:
            self.spans.append(
                Span(f"{label}:{name}", start_s + float(base_s),
                     dur_s, base_depth + (depth - dmin))
            )

    def rows(self):
        out = []
        for s in sorted(self.spans, key=lambda s: s.start_s):
            out.append(
                ("  " * (s.depth - 1) + s.name, f"{s.start_s*1e3:.3f}ms", f"{s.dur_s*1e3:.3f}ms")
            )
        return out

    def totals_by_name(self) -> dict:
        """Total duration per span name. A TRACE'd statement's plan /
        execute rows and the flight recorder's phase charges
        (obs/flight.py) come from the same FLIGHT.span call, so they
        agree by construction — tests/test_observability asserts it."""
        out: dict = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.dur_s
        return out


# module-level convenience tracer used when no session is involved
_global = Tracer()


def span(name: str):
    return _global.span(name)
