"""From a profiler trace to numbers. The one reduction every PR uses.

A trace is held in a plain form (``Trace``): per device plane the events
of its ``XLA Ops`` and ``XLA Modules`` lines, and the host spans the
benchmark's own ``jax.profiler.TraceAnnotation`` calls wrote (names that
start with ``perf.``), all as ``(name, start_ns, dur_ns)`` on the
profiler's one clock. ``load_xplane`` fills it from the ``.xplane.pb``
file with nothing but JAX; ``perf/tests/data/`` holds a small one written
by hand in the same form, which ``perf/tests/test_trace_reduce.py`` checks
this file against.

What a v5e trace looks like (PR 22's probe): plane ``/device:TPU:<i>`` with
lines ``Steps``, ``XLA Modules`` (events ``jit_<fn>(<id>)``), ``XLA Ops``
(event name = the HLO instruction's text, ``%fusion.257 = f32[512]{...}
fusion(...)``; a Pallas kernel's instruction is named after the kernel,
wrapped as ``transpose_jvp_<kernel>__`` under autodiff) and ``Async XLA
Ops``; plane ``/host:CPU`` holds the annotations.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # name, start_ns, dur_ns
SPAN_PREFIX = "perf."
WINDOW_SPAN = "perf.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "host": self.host}

    @staticmethod
    def from_json(d: dict) -> "Trace":
        as_events = lambda evs: [(n, float(s), float(t)) for n, s, t in evs]
        return Trace({p: as_events(e) for p, e in d["ops"].items()},
                     {p: as_events(e) for p, e in d["modules"].items()},
                     as_events(d["host"]))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[plane.name] = [
                        (e.name[:_NAME_CHARS], e.start_ns, e.duration_ns)
                        for e in line.events
                        if not _CONTAINERS.search(e.name)]
                elif line.name == "XLA Modules":
                    tr.modules[plane.name] = [
                        (e.name[:_NAME_CHARS], e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host.extend(
                    (e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    tr.host.sort(key=lambda e: (e[1], -e[2]))
    return tr


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

_HLO = re.compile(r"^%?([^\s=]+)\s*=\s*\(?\s*([a-z]+[0-9]*)\[([0-9,]*)\]")
_WRAPPERS = ("transpose_", "jvp_", "vmap_", "remat_", "checkpoint_")
#: instructions whose event spans the events of a body (a scan's while):
#: left out when a trace is loaded, since they would hide the gaps between
#: the body's operations and be counted twice in a sum by name
_CONTAINERS = re.compile(r"\s(while|conditional|call)\(")
#: an event's name is the instruction's whole text, a kernel's with its
#: serialized body: the head is all the reduction reads
_NAME_CHARS = 240


def op_label(hlo_text: str) -> str:
    """``%flash_decode.3 = f32[96,16,1,64]{...} custom-call(...)`` ->
    ``flash_decode_f32_96_16_1_64_``: instruction name without its
    numeric suffix, then the (first) result's type and shape. The form
    PR 22's ledger rows use."""
    m = _HLO.match(hlo_text)
    if not m:
        return re.sub(r"[^A-Za-z0-9_.-]", "_", hlo_text.split(" ")[0])[:64]
    name = re.sub(r"\.\d+$", "", m.group(1))
    name = re.sub(r"[^A-Za-z0-9_-]", "_", name)
    dims = "_".join(d for d in m.group(3).split(",") if d)
    return f"{name}_{m.group(2)}_{dims}_"


def kernel_of(hlo_text: str) -> str:
    """The Pallas kernel an ``XLA Ops`` event belongs to: its instruction
    name with the numeric suffix, autodiff wrappers (``transpose(jvp(..))``
    printed as ``transpose_jvp_..__``) and trailing underscores removed.
    Exact names only, so ``paged_flash_decode`` is never ``flash_decode``."""
    name = hlo_text.lstrip("%").split(" ", 1)[0].split("=", 1)[0]
    name = re.sub(r"\.\d+$", "", name)
    name = re.sub(r"[^A-Za-z0-9_]", "_", name).strip("_")
    stripped = True
    while stripped:
        stripped = False
        for w in _WRAPPERS:
            if name.startswith(w):
                name, stripped = name[len(w):].strip("_"), True
    return name


def module_of(event_name: str) -> str:
    """``jit_round_fn(4562773413465019209)`` -> ``jit_round_fn``."""
    return event_name.split("(", 1)[0]


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def _clip(events: Iterable[Event], t0: float, t1: float
          ) -> List[Tuple[float, float]]:
    out = []
    for _n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Disjoint sorted cover of ``intervals``."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: Sequence[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """What ``busy`` (disjoint, sorted) leaves free of ``[t0, t1]``."""
    out, at = [], t0
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def innermost_timeline(spans: Sequence[Event]
                       ) -> Tuple[List[float], List[Optional[str]]]:
    """Piecewise-constant "which span is innermost at time t": the span
    that covers t and started last. Returns (boundaries, name from each
    boundary to the next); the last name is None."""
    points = sorted({p for _n, s, d in spans for p in (s, s + d)})
    names: List[Optional[str]] = []
    ordered = sorted(spans, key=lambda e: e[1])
    for i, p in enumerate(points):
        best = None
        for n, s, d in ordered:
            if s > p:
                break
            if s + d > p and (best is None or s >= best[1]):
                best = (n, s)
        names.append(best[0] if best and i + 1 < len(points) else None)
    return points, names


def charge_gaps(idle: Sequence[Tuple[float, float]],
                spans: Sequence[Event]) -> Dict[str, float]:
    """Seconds of device idleness by what the host was doing: every piece
    of every gap goes to the innermost host span that covers it, or to
    ``unannotated``."""
    points, names = innermost_timeline(spans)
    out: Dict[str, float] = {}
    for a, b in idle:
        at = a
        i = bisect.bisect_right(points, a) - 1
        while at < b:
            nxt = points[i + 1] if i + 1 < len(points) else b
            end = min(b, nxt) if nxt > at else b
            name = names[i] if 0 <= i < len(names) else None
            key = (name[len(SPAN_PREFIX):] if name else "unannotated")
            out[key] = out.get(key, 0.0) + (end - at) * 1e-9
            at, i = end, i + 1
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over device planes
    n_devices: int
    op_seconds: Dict[str, float]        # op_label -> s, summed over planes
    kernel_seconds: Dict[str, float]    # kernel_of -> s, mean over planes
    kernel_calls: Dict[str, int]        # kernel_of -> events on one plane
    module_ms: Dict[str, List[float]]   # module_of -> every duration, ms
    idle_by_span: Dict[str, float]      # plane 0's gaps, s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        rank = lambda d: [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(self.op_seconds),
                "idle_gaps": rank(self.idle_by_span)}


def reduce_trace(tr: Trace, window: str = WINDOW_SPAN,
                 need_device: bool = True) -> Reduced:
    """Everything the per-layer readers take from a trace, over the
    interval of the host span called ``window`` (the traced units).
    ``need_device=False`` is the CPU rehearsal's: a trace without a device
    plane reduces to no device time at all."""
    wins = [e for e in tr.host if e[0] == window]
    if len(wins) != 1:
        raise ValueError(f"expected one {window!r} span in the trace, "
                         f"found {len(wins)}")
    t0, t1 = wins[0][1], wins[0][1] + wins[0][2]
    planes = sorted(tr.ops)
    if not planes and need_device:
        raise ValueError("the trace holds no device plane with XLA Ops")
    spans = [e for e in tr.host if e[0] != window
             and e[1] < t1 and e[1] + e[2] > t0]
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    kern_s: Dict[str, float] = {}
    kern_n: Dict[str, int] = {}
    idle_by_span: Dict[str, float] = {}
    for i, plane in enumerate(planes):
        events = tr.ops[plane]
        busy = union(_clip(events, t0, t1))
        busy_total += sum(b - a for a, b in busy) * 1e-9
        for name, s, d in events:
            a, b = max(s, t0), min(s + d, t1)
            if b <= a:
                continue
            sec = (b - a) * 1e-9
            label = op_label(name)
            op_s[label] = op_s.get(label, 0.0) + sec
            k = kernel_of(name)
            kern_s[k] = kern_s.get(k, 0.0) + sec
            if i == 0:
                kern_n[k] = kern_n.get(k, 0) + 1
        if i == 0:
            idle_by_span = charge_gaps(gaps(busy, t0, t1), spans)
    n = max(1, len(planes))
    mods: Dict[str, List[float]] = {}
    for plane in sorted(tr.modules):
        for name, s, d in tr.modules[plane]:
            if s >= t0 and s + d <= t1:
                mods.setdefault(module_of(name), []).append(d * 1e-6)
    return Reduced(
        window_s=(t1 - t0) * 1e-9, busy_s=busy_total / n,
        n_devices=len(planes),
        op_seconds=op_s,
        kernel_seconds={k: v / n for k, v in kern_s.items()},
        kernel_calls=kern_n, module_ms=mods, idle_by_span=idle_by_span)
