"""The program's own host spans in a profiler trace: which span
dispatched each device launch, and which span the host was in while
the device idled.

The program marks its layer boundaries with ``jax.profiler``
annotations (:data:`SPANS`), on the profiler's own clock. This module
opens the same ``.xplane.pb`` that ``Tracer.load`` opens and ties the
device to those spans in two ways.

- **Launches.** Every XLA module event on a device plane carries the
  ``run_id`` of its launch, and so does the host's ``DoEnqueueProgram``
  event that queued it. The Python thread dispatches each launch in a
  ``PJRT_LoadedExecutable_Execute`` call. Where the enqueue ran inside
  that call, on the same thread, the pair is read directly (an
  anchor). Where the runtime enqueued it later, on a task thread, the
  dispatch is found by order: run ids grow in dispatch order, so
  between two anchors the i-th unpaired dispatch took the i-th
  unpaired run id. A launch then belongs to the innermost program span
  open when its dispatch began, or to none.
- **Idle.** The stretches of the traced window in which device 0 ran
  no op (the complement of the union of op intervals that
  ``Trace.busy_s`` takes) are cut at the span boundaries, and each
  piece goes to the innermost program span open on the host then, or
  to none.

The program dispatches from one Python thread, so a span that is open
when a dispatch begins encloses it. A metric file reads one of
:class:`Spans`'s sums and returns ``None`` where its spans are absent
(a checkout whose program has no such span, or a trace without a
device).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

from bench.tracing import (DEVICE_PLANE, MODULE_LINE, Trace, module_name,
                           union_ns)

#: the program's spans, as ``repro`` names them
SPANS = ("engine.init", "engine.superstep", "sink.insert", "index.query",
         "store.put", "store.fetch")
#: the Python thread's call that dispatches one launch
DISPATCH = "PJRT_LoadedExecutable_Execute"
#: the runtime's event that queues one launch, with its ``run_id``
ENQUEUE = "DoEnqueueProgram"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    parent: Optional[int] = None     # index of the enclosing span


@dataclasses.dataclass
class Launch:
    name: str                        # XLA module, without its id
    start: float
    end: float
    run_id: Optional[int]
    span: Optional[int] = None       # innermost span that dispatched it


@dataclasses.dataclass
class Dispatch:
    start: float
    end: float
    run_id: Optional[int] = None
    span: Optional[int] = None


def trace_path(tracer) -> Optional[str]:
    """The ``.xplane.pb`` that ``Tracer.load`` reads, or ``None``."""
    if tracer is None or tracer.directory is None:
        return None
    paths = sorted(glob.glob(os.path.join(
        tracer.directory, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def read(trace: Trace, ctx) -> Optional["Spans"]:
    """The spans of the run's trace, or ``None`` where the trace has no
    device or holds none of the program's spans."""
    path = trace_path(ctx.tracer)
    if path is None or not trace.has_device():
        return None
    spans = Spans.from_file(path, trace)
    return spans if spans.spans else None


def pair_dispatches(dispatches: List[Dispatch],
                    anchors: Dict[int, int],
                    run_ids: Iterable[int]) -> None:
    """Set ``run_id`` of each dispatch (sorted by start): the anchors
    (dispatch index -> run id) as read, the rest by order between
    them. Before the first anchor the pairing counts back from it,
    after the last one forward from it (the trace may begin or end
    between a dispatch and its enqueue); between two anchors whose
    gaps hold different counts, nothing is paired."""
    for i, rid in anchors.items():
        dispatches[i].run_id = rid
    taken = set(anchors.values())
    free_ids = sorted(r for r in set(run_ids) if r not in taken)
    cuts = sorted(anchors.items())          # (dispatch index, run id)
    bounds = [(-1, None)] + cuts + [(len(dispatches), None)]
    for (i0, r0), (i1, r1) in zip(bounds, bounds[1:]):
        ds = [i for i in range(i0 + 1, i1)]
        lo = 0 if r0 is None else bisect.bisect_right(free_ids, r0)
        hi = (len(free_ids) if r1 is None
              else bisect.bisect_left(free_ids, r1))
        ids = free_ids[lo:hi]
        if r0 is None and r1 is not None:        # count back
            k = min(len(ds), len(ids))
            pairs = zip(ds[len(ds) - k:], ids[len(ids) - k:])
        elif r1 is None and r0 is not None:      # count forward
            pairs = zip(ds, ids)
        elif len(ds) == len(ids):
            pairs = zip(ds, ids)
        else:
            continue
        for i, rid in pairs:
            dispatches[i].run_id = rid


def _nest(spans: List[Span]) -> List[Span]:
    """Spans sorted by start, each with its enclosing span."""
    spans.sort(key=lambda s: (s.start, -s.end))
    stack: List[int] = []
    for i, s in enumerate(spans):
        while stack and spans[stack[-1]].end < s.end:
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(i)
    return spans


def _pieces(spans: List[Span]) -> List[Tuple[float, float, int]]:
    """Host time cut at every span boundary into the pieces in which
    some span is open, each with the innermost one open; ``spans``
    sorted as :func:`_nest` leaves them."""
    out: List[Tuple[float, float, int]] = []
    stack: List[int] = []
    t = 0.0

    def close(until: float) -> None:
        nonlocal t
        while stack and spans[stack[-1]].end <= until:
            i = stack.pop()
            if spans[i].end > t:
                out.append((t, spans[i].end, i))
                t = spans[i].end

    for i, s in enumerate(spans):
        close(s.start)
        if stack and s.start > t:
            out.append((t, s.start, stack[-1]))
        stack.append(i)
        t = s.start
    close(float("inf"))
    return out


class Spans:
    """The program spans of one trace, with the launches they
    dispatched and the device idle time spent in them."""

    def __init__(self, trace: Trace, spans: List[Span],
                 launches: List[Launch], dispatches: List[Dispatch]):
        self.trace = trace
        self.spans = _nest(spans)
        self.launches = launches
        self.dispatches = dispatches
        self._pieces = _pieces(self.spans)
        starts = [lo for lo, _, _ in self._pieces]
        for d in dispatches:
            k = bisect.bisect_right(starts, d.start) - 1
            if k >= 0 and d.start < self._pieces[k][1]:
                d.span = self._pieces[k][2]
        by_run = {d.run_id: d.span for d in dispatches
                  if d.run_id is not None}
        for launch in launches:
            launch.span = by_run.get(launch.run_id)

    @classmethod
    def from_file(cls, path: str, trace: Trace) -> "Spans":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        spans: List[Span] = []
        launches: List[Launch] = []
        lines: List[Tuple[List[Dispatch], List[Tuple[float, int]]]] = []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name != MODULE_LINE:
                        continue
                    for e in line.events:
                        launches.append(Launch(
                            module_name(e.name), e.start_ns, e.end_ns,
                            dict(e.stats).get("run_id")))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    ds, enq = [], []
                    for e in line.events:
                        if e.name in SPANS:
                            spans.append(Span(e.name, e.start_ns, e.end_ns))
                        elif e.name == DISPATCH:
                            ds.append(Dispatch(e.start_ns, e.end_ns))
                        elif e.name == ENQUEUE:
                            rid = dict(e.stats).get("run_id")
                            if rid is not None:
                                enq.append((e.start_ns, int(rid)))
                    lines.append((ds, enq))
        dispatches = sorted((d for ds, _ in lines for d in ds),
                            key=lambda d: d.start)
        index = {id(d): i for i, d in enumerate(dispatches)}
        anchors: Dict[int, int] = {}
        run_ids = []
        for ds, enq in lines:
            ds.sort(key=lambda d: d.start)
            starts = [d.start for d in ds]
            for t, rid in enq:
                run_ids.append(rid)
                j = bisect.bisect_right(starts, t) - 1
                if j >= 0 and t <= ds[j].end:
                    anchors[index[id(ds[j])]] = rid
        pair_dispatches(dispatches, anchors, run_ids)
        return cls(trace, spans, launches, dispatches)

    # ----------------------------------------------------- structure

    def around(self, i: Optional[int], name: str) -> Optional[int]:
        """The outermost span named ``name`` that is span ``i`` or holds
        it, or ``None``."""
        out = None
        while i is not None:
            if self.spans[i].name == name:
                out = i
            i = self.spans[i].parent
        return out

    def named(self, name: str) -> List[int]:
        return [i for i, s in enumerate(self.spans) if s.name == name]

    # ------------------------------------------------------ launches

    def launch_s(self) -> Dict[Optional[str], float]:
        """Device seconds in the window of every launch, by the name of
        the innermost span that dispatched it (``None``: no span)."""
        lo_w, hi_w = self.trace.window
        out: Dict[Optional[str], float] = {}
        for launch in self.launches:
            key = (None if launch.span is None
                   else self.spans[launch.span].name)
            inside = max(0.0, min(launch.end, hi_w) - max(launch.start,
                                                          lo_w))
            out[key] = out.get(key, 0.0) + inside * 1e-9
        return out

    def whole_spans(self, name: str) -> Dict[int, List[Launch]]:
        """Each span named ``name`` whose launches (its children's
        included) all lie wholly inside the window, with those
        launches: every dispatch in it is paired to a launch the trace
        holds, and no launch is cut by the window's edge."""
        lo_w, hi_w = self.trace.window
        by_run = {launch.run_id: launch for launch in self.launches}
        out: Dict[int, List[Launch]] = {i: [] for i in self.named(name)}
        broken = set()
        for d in self.dispatches:
            top = self.around(d.span, name)
            if top is None:
                continue
            launch = by_run.get(d.run_id)
            if (launch is None or launch.start < lo_w
                    or launch.end > hi_w):
                broken.add(top)
            else:
                out[top].append(launch)
        return {i: ls for i, ls in out.items() if i not in broken}

    # ---------------------------------------------------------- idle

    def idle_ns(self) -> Dict[Optional[int], float]:
        """Device 0's idle nanoseconds in the window, by the innermost
        span open on the host (``None``: no span open)."""
        lo_w, hi_w = self.trace.window
        cover = union_ns((max(lo, lo_w), min(hi, hi_w))
                         for _, lo, hi in self.trace.ops[0]
                         if hi > lo_w and lo < hi_w)
        gaps, t = [], lo_w
        for lo, hi in cover:
            if lo > t:
                gaps.append((t, lo))
            t = max(t, hi)
        if hi_w > t:
            gaps.append((t, hi_w))
        out: Dict[Optional[int], float] = {}
        total = sum(hi - lo for lo, hi in gaps)
        j = 0
        for lo, hi in gaps:
            while j < len(self._pieces) and self._pieces[j][1] <= lo:
                j += 1
            k = j
            while k < len(self._pieces) and self._pieces[k][0] < hi:
                p_lo, p_hi, i = self._pieces[k]
                cut = min(hi, p_hi) - max(lo, p_lo)
                if cut > 0:
                    out[i] = out.get(i, 0.0) + cut
                k += 1
        out[None] = total - sum(out.values())
        return out

    def _idle_pct(self, keep) -> float:
        lo_w, hi_w = self.trace.window
        ns = sum(v for i, v in self.idle_ns().items() if keep(i))
        return 100.0 * ns / (hi_w - lo_w)

    def idle_pct_in(self, name: str) -> float:
        """Share of the window in which the device idled while a span
        named ``name`` was open on the host."""
        return self._idle_pct(lambda i: self.around(i, name) is not None)

    def idle_pct_outside(self, name: str) -> float:
        """Share of the window in which the device idled and no span
        named ``name`` was open on the host."""
        return self._idle_pct(lambda i: self.around(i, name) is None)
