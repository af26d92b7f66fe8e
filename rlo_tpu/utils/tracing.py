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
    jax.profiler.trace for a capture window.

The native C core has the same facility (rlo_trace_* in rlo_core.h);
tests assert both sides emit the same event sequence for the same
scenario.
"""

from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from dataclasses import dataclass, field
from enum import IntEnum
from typing import Deque, Dict, Iterator, List, Optional


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
    SpanRecorder gets the same stage as an ``Ev.SPAN``. Always on: no
    flag arms it. The jax import is lazy (the engine stack imports
    this module without JAX)."""
    __slots__ = ("_ann", "_metrics", "_counter", "_emit", "_t0")

    def __init__(self, name: str, metrics=None,
                 counter: Optional[str] = None, emit=None, **ids):
        global _TraceAnnotation
        if _TraceAnnotation is None:
            from jax.profiler import TraceAnnotation
            _TraceAnnotation = TraceAnnotation
        self._ann = _TraceAnnotation(name, **ids)
        self._metrics = metrics
        self._counter = name if counter is None else counter
        self._emit = emit

    def __enter__(self) -> "annotate":
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self._metrics is not None:
            self._metrics.counter(self._counter + "_ns").inc(
                t1 - self._t0)
            self._metrics.counter(self._counter + "_n").inc()
        if self._emit is not None:
            self._emit(self._t0 * 1e-9, t1 * 1e-9)


@contextlib.contextmanager
def profile(logdir: str):
    """Capture a jax profiler trace window into ``logdir``."""
    import jax.profiler
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
