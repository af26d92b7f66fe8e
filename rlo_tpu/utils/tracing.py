"""Structured per-op event tracing + jax.profiler integration.

The reference has no tracing beyond gettimeofday timestamps bracketing
test loops and commented-out printf tracepoints (SURVEY.md §5:
rootless_ops.c:128-132, the unused Log/DEBUG_MODE globals :116-121).
This is the rebuild's replacement:

  - a process-local structured event log (`Tracer`): bounded ring of
    (usec, rank, kind, fields) records appended by the progress engine
    at every protocol step — bcast initiate/forward/deliver, proposal
    judge/vote/decision — cheap enough to leave compiled in (one branch
    when disabled), drainable as dicts or JSONL;
  - device-side: `annotate(name)` is the one span helper — a
    jax.profiler.TraceAnnotation (so host stages and collective
    launches show up named in TPU profiles, on the profiler's clock)
    that also totals its elapsed time into a metrics Registry and can
    hand its bracket to a fabric SpanRecorder; `profile(logdir)` wraps
    jax.profiler.trace for a capture window;
  - `BUILDS`, the process's build log: every trace, lowering and
    compile JAX makes, one record a root with nested time apart, under
    the `annotate` span that was open when it began.

The native C core has the same facility (rlo_trace_* in rlo_core.h);
tests assert both sides emit the same event sequence for the same
scenario.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from enum import IntEnum
from typing import Deque, Dict, Iterable, Iterator, List, Optional


class Ev(IntEnum):
    """Event kinds — numbering AND field semantics shared with the C
    core (rlo_core.h enum rlo_ev). ``c``/``d`` carry the correlation
    identity the cross-rank timeline merger keys on: for store-and-
    forward frames the identity is (origin, seq) for Tag.BCAST —
    every initiated broadcast is stamped with a per-origin sequence
    number — and (origin, pid) for IAR/FAILURE/ABORT traffic; ``d``
    is the immediate sender, which is what turns per-rank event logs
    into send->recv flow edges (rlo_tpu/utils/timeline.py)."""
    BCAST_INIT = 1      # a = tag, b = payload len, c = seq (BCAST) / pid
    BCAST_FWD = 2       # receipt+forward step of a store-and-forward
    #                     frame: a = tag, b = origin, c = seq/pid,
    #                     d = immediate sender (emitted even for leaf
    #                     receipts with zero forward targets)
    DELIVER = 3         # a = tag, b = origin, c = seq/pid, d = sender
    PROPOSAL_SUBMIT = 4  # a = pid, c = round generation
    JUDGE = 5           # a = pid, b = verdict
    VOTE = 6            # a = pid, b = merged vote, c = generation
    DECISION = 7        # a = pid, b = decision, c = generation
    DRAIN = 8           # a = spins
    HEARTBEAT = 9       # a = destination rank
    FAILURE = 10        # a = failed rank, b = 1 local detection /
    #                     0 learned; c = last-seen heartbeat age (usec,
    #                     clamped to int32) on local detections
    ARQ_GIVEUP = 11     # ARQ exhausted its retries at a live peer and
    #                     the peer is being declared failed: a = peer,
    #                     b = retransmit count of the abandoned frame
    JOIN = 12           # membership probe: a = peer, b = 1 sent /
    #                     0 received, c = incarnation, d = epoch
    ADMIT = 13          # membership admission executed: a = joiner,
    #                     b = new epoch, c = joiner incarnation
    PHASE = 14          # phase-profiler stage sample (docs/DESIGN.md
    #                     §10): a = phase index in the
    #                     metrics.ENGINE_PHASE_KEYS snapshot order,
    #                     b = duration (usec, clamped to int32); the
    #                     timeline merger renders it as a Chrome
    #                     duration slice ENDING at ts_usec
    SPAN = 15           # request-scoped causal span (docs/DESIGN.md
    #                     §19): a = stage id (observe.spans.Stage),
    #                     b = stage duration (usec, clamped to int32;
    #                     -1 marks a wire-hop receipt of a span-stamped
    #                     record rather than a stage boundary),
    #                     c = rid seq, d = rid gateway. Emitted with an
    #                     explicit engine-clock ts_usec (stage END) so
    #                     traced fleets replay bit-for-bit in the
    #                     deterministic simulator
    STEP = 16           # collective data-plane step (docs/DESIGN.md
    #                     §21): a = schedule id (observe.ledger
    #                     .ALGORITHMS index), b = step duration (usec,
    #                     clamped to int32) measured completion-to-
    #                     completion at this rank, c = op id * 1024 +
    #                     step index (the cross-rank join identity —
    #                     SPMD ranks issue ops in identical order),
    #                     d = the rank this step RECEIVED from (-1 for
    #                     send-only steps). Emitted at step END with an
    #                     explicit injectable-clock ts_usec; payload
    #                     bytes are deliberately NOT in the event —
    #                     rlo-scope joins them from the cost ledger,
    #                     which instrumentation can therefore never
    #                     contradict silently


@dataclass
class Event:
    ts_usec: int
    rank: int
    kind: Ev
    a: int = 0
    b: int = 0
    c: int = 0
    d: int = 0

    def to_dict(self) -> Dict:
        return {"ts_usec": self.ts_usec, "rank": self.rank,
                "kind": self.kind.name, "a": self.a, "b": self.b,
                "c": self.c, "d": self.d}


@dataclass
class Tracer:
    """Bounded structured event log; disabled by default."""
    capacity: int = 65536
    enabled: bool = False
    _events: Deque[Event] = field(default_factory=deque)
    dropped: int = 0

    def emit(self, rank: int, kind: Ev, a: int = 0, b: int = 0,
             c: int = 0, d: int = 0,
             ts_usec: Optional[int] = None) -> None:
        """``ts_usec`` overrides the wall-clock stamp — span emitters
        pass the engine's injectable clock so traced runs stay
        deterministic under the simulator (R5)."""
        if not self.enabled:
            return
        if len(self._events) >= self.capacity:
            self._events.popleft()
            self.dropped += 1
        self._events.append(
            Event(int(time.time() * 1e6) if ts_usec is None else ts_usec,
                  rank, kind, a, b, c, d))

    def events(self, kind: Optional[Ev] = None,
               rank: Optional[int] = None) -> List[Event]:
        return [e for e in self._events
                if (kind is None or e.kind == kind)
                and (rank is None or e.rank == rank)]

    def clear(self) -> None:
        self._events.clear()
        self.dropped = 0

    def dump_jsonl(self, path: str, rank: Optional[int] = None) -> int:
        """Write events as JSON lines; ``rank`` filters to one rank's
        events (the per-rank dump shape rlo_tpu/utils/timeline.py
        merges — in multi-process deployments each process dumps its
        own ranks)."""
        n = 0
        with open(path, "w") as f:
            for e in self._events:
                if rank is not None and e.rank != rank:
                    continue
                f.write(json.dumps(e.to_dict()) + "\n")
                n += 1
        return n

    @contextlib.contextmanager
    def enable(self) -> Iterator["Tracer"]:
        prev = self.enabled
        self.enabled = True
        try:
            yield self
        finally:
            self.enabled = prev


#: default process-wide tracer the engines emit into
TRACER = Tracer()


# ---------------------------------------------------------------------------
# Device-side: jax.profiler hooks
# ---------------------------------------------------------------------------

_TraceAnnotation = None  # jax.profiler.TraceAnnotation, on first use

#: per thread: ``spans``, the open ``annotate`` spans, outermost first,
#: and the build log's open events (``BuildLog``)
_LOCAL = threading.local()


class annotate:
    """The span helper: ``with annotate(name, ...):`` around a stage.

    Entering opens ``jax.profiler.TraceAnnotation(name, **ids)``: under
    a profiler session the stage is a named region on the profiler's
    own clock, beside the device planes (``ids`` ride as its
    arguments); with no session it costs about a microsecond. Leaving
    adds the elapsed ``time.perf_counter_ns()`` to the counter
    ``<counter>_ns`` of ``metrics`` and 1 to ``<counter>_n``
    (``counter`` defaults to ``name``; no ``metrics``, no counters),
    and calls ``emit(t0, t1)`` with the bracket in
    ``time.perf_counter()`` seconds when one is given — how a fabric's
    SpanRecorder gets the same stage as an ``Ev.SPAN``. While it is
    open it is the innermost of its thread's open spans: a program
    that JAX builds meanwhile is recorded in ``BUILDS`` under its name
    and counted into its ``metrics`` (``Build``). Always on: no flag
    arms it. The jax import is lazy (the engine stack imports this
    module without JAX)."""
    __slots__ = ("name", "metrics", "counter", "_ann", "_emit", "_t0")

    def __init__(self, name: str, metrics=None,
                 counter: Optional[str] = None, emit=None, **ids):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation
            _TraceAnnotation = TraceAnnotation
            BUILDS.arm()
        self.name = name
        self.metrics = metrics
        self.counter = name if counter is None else counter
        self._ann = _TraceAnnotation(name, **ids)
        self._emit = emit

    def __enter__(self) -> "annotate":
        self._ann.__enter__()
        try:
            _LOCAL.spans.append(self)
        except AttributeError:      # the thread's first span
            _LOCAL.spans = [self]
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        _LOCAL.spans.pop()
        self._ann.__exit__(*exc)
        if self.metrics is not None:
            self.metrics.counter(self.counter + "_ns").inc(
                t1 - self._t0)
            self.metrics.counter(self.counter + "_n").inc()
        if self._emit is not None:
            self._emit(self._t0 * 1e-9, t1 * 1e-9)


# ---------------------------------------------------------------------------
# The build log: what JAX traced, lowered and compiled, and for whom
# ---------------------------------------------------------------------------

#: JAX's monitoring events around the three phases of obtaining a
#: program. Each fires on entry (a scalar: its start) and on exit (a
#: time span), both with ``fun_name=``; the events of programs built
#: inside a phase (a jitted callee's trace in its caller's, a
#: primitive's in a lowering rule) fire inside its interval.
_BUILD_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: the persistent cache's answer, fired inside a compile's interval
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
#: what a ``Build`` counts, and the counters ``<prefix>.build.<key>`` it
#: adds to its span's registry: programs obtained (compiled, or read
#: from the persistent cache), nanoseconds by phase, each once
#: (``trace_ns`` in a trace that is a root, ``trace_nested_ns`` in
#: traces opened inside another event's interval), and the persistent
#: cache's answers
BUILD_COUNTS = ("programs", "trace_ns", "trace_nested_ns", "lower_ns",
                "compile_ns", "cache_hits", "cache_misses")
#: phase -> where an event's own nanoseconds go: as a root, nested
_PHASE_NS = {"trace": ("trace_ns", "trace_nested_ns"),
             "lower": ("lower_ns", "lower_ns"),
             "compile": ("compile_ns", "compile_ns")}


class Build:
    """One root of the build log: a trace, lowering or compile that
    began with no other open on its thread. ``fun_name`` is the
    function's name as JAX gives it (``jit(f)`` read as ``f``),
    ``phase`` the root's own, ``t0`` / ``t1`` its interval on
    ``time.perf_counter_ns()``, ``span`` the name of the innermost
    ``annotate`` open when it began (None: none was), ``metrics`` that
    span's registry and ``prefix`` what its counters there start with
    (the span's counter up to its first dot, then ``.build.``).
    ``counts`` holds BUILD_COUNTS over the whole interval: an event
    opened inside it is folded in as it closes, its own time (its
    duration less what its children cover) under its phase, so the
    four ``*_ns`` add up to ``t1 - t0`` and no nanosecond is counted
    twice."""
    __slots__ = ("fun_name", "phase", "span", "metrics", "prefix", "t0",
                 "t1", "counts")

    def __init__(self, fun_name: str, phase: str,
                 span: Optional[annotate], t0: int):
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        self.fun_name = fun_name
        self.phase = phase
        self.span = self.metrics = self.prefix = None
        if span is not None:
            self.span, self.metrics = span.name, span.metrics
            self.prefix = span.counter.partition(".")[0] + ".build."
        self.t0 = self.t1 = t0
        self.counts = dict.fromkeys(BUILD_COUNTS, 0)


class BuildLog:
    """Every program this process obtained, as ``records``: one
    ``Build`` a root, the newest ``capacity`` of them. It listens to
    JAX's own monitoring events, so it sees what any caller builds,
    jitted or eager, and keeps a true stack a thread (``_LOCAL.builds``:
    the open events as [phase, start ns, ns its children cover];
    ``_LOCAL.root``: the record of the outermost). A listener call is
    an append or a pop, and memory is the nesting depth plus one record
    a root: a 24-layer server's start fires 10^4-10^5 events and keeps
    a few hundred records. Times are stamped as the events arrive, on
    the clock ``annotate`` totals on. ``events`` counts the listener
    calls that found a phase (what the log costs is that many times a
    call)."""

    def __init__(self, capacity: int = 16384):
        self.records: Deque[Build] = deque(maxlen=capacity)
        self.events = 0
        self._armed = False

    def arm(self) -> None:
        """Register the listeners, once. ``annotate`` does on its first
        use; a main that wants the programs built before its first
        span calls it itself."""
        if self._armed:
            return
        self._armed = True
        from jax import monitoring
        monitoring.register_scalar_listener(self._on_enter)
        monitoring.register_event_time_span_listener(self._on_exit)
        monitoring.register_event_listener(self._on_cache)

    def _on_enter(self, event: str, _start, fun_name: str = "",
                  **_kw) -> None:
        phase = _BUILD_PHASES.get(event)
        if phase is None:
            return
        now = time.perf_counter_ns()
        self.events += 1
        open_ = getattr(_LOCAL, "builds", None)
        if open_ is None:
            open_ = _LOCAL.builds = []
        if not open_:
            spans = getattr(_LOCAL, "spans", None)
            _LOCAL.root = Build(fun_name, phase,
                                spans[-1] if spans else None, now)
        open_.append([phase, now, 0])

    def _on_exit(self, event: str, _start, _end, **_kw) -> None:
        phase = _BUILD_PHASES.get(event)
        if phase is None:
            return
        now = time.perf_counter_ns()
        open_ = getattr(_LOCAL, "builds", None)
        if not open_:
            return      # armed inside this event: it was never pushed
        self.events += 1
        _, t0, covered = open_.pop()
        root = _LOCAL.root
        root.counts[_PHASE_NS[phase][bool(open_)]] += now - t0 - covered
        if phase == "compile":
            root.counts["programs"] += 1
        if open_:
            open_[-1][2] += now - t0
            return
        root.t1 = now
        self.records.append(root)
        if root.metrics is not None:
            for key, n in root.counts.items():
                root.metrics.counter(root.prefix + key).inc(n)

    def _on_cache(self, event: str, **_kw) -> None:
        key = _CACHE_EVENTS.get(event)
        if key is not None and getattr(_LOCAL, "builds", None):
            _LOCAL.root.counts[key] += 1


#: the process-wide build log
BUILDS = BuildLog()


def build_totals(records: Iterable[Build]) -> Dict[str, int]:
    """BUILD_COUNTS summed over ``records``."""
    total = dict.fromkeys(BUILD_COUNTS, 0)
    for r in records:
        for key, n in r.counts.items():
            total[key] += n
    return total


def build_table(records: Iterable[Build]) -> List[Dict]:
    """``records`` by ``fun_name``, the costliest first: ``calls`` (the
    roots of the phase that has most: a jitted call is a trace, a
    lowering and a compile), seconds of ``trace_s`` (the roots' own),
    ``trace_nested_s``, ``lower_s`` and ``compile_s``, each once, and
    their sum ``total_s``, ``programs``, ``cache_hits`` and
    ``cache_misses``, and ``spans``, the names of the spans its roots
    began under."""
    by_name: Dict[str, List[Build]] = {}
    for r in records:
        by_name.setdefault(r.fun_name, []).append(r)
    table = []
    for fun_name, group in by_name.items():
        phases = [r.phase for r in group]
        row = {"fun_name": fun_name,
               "calls": max(phases.count(p) for p in _PHASE_NS)}
        total = 0
        for key, n in build_totals(group).items():
            if key.endswith("_ns"):
                row[key[:-2] + "s"] = n / 1e9
                total += n
            else:
                row[key] = n
        row["total_s"] = total / 1e9
        row["spans"] = sorted({r.span for r in group} - {None})
        table.append(row)
    table.sort(key=lambda row: -row["total_s"])
    return table


@contextlib.contextmanager
def profile(logdir: str):
    """Capture a jax profiler trace window into ``logdir``."""
    import jax.profiler
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
