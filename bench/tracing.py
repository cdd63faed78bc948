"""Profiler traces: taking one of a part of the window, and reducing it.

:class:`Tracer` runs JAX's profiler over the first seconds of a
driver's window, under a host span ``bench.window`` that fixes the
traced window on the trace's own clock. Drivers add host spans of
their own (``Tracer.span``) around each call into a layer of the
program.

:class:`Trace` reads the ``.xplane.pb`` the profiler writes. A device
plane (``/device:TPU:<i>``) holds one line of XLA modules (one event
per launch of a jitted program, named ``jit_<function>(<id>)``) and
one of XLA ops. Busy time is the union of op intervals inside the
window, averaged over the devices; a module's time is the sum of its
launch events inside the window, and a launch's own time counts only
when the launch lies wholly inside it. Per-layer metrics map module
names to layers in their own files.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import threading
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
#: host events never used to name an idle gap
_NOT_A_CAUSE = {WINDOW_SPAN}


class Tracer:
    """Takes one profiler trace into ``directory``; with ``None``, its
    window and spans do nothing (an untraced run).

    ``window(seconds)`` wraps a driver's whole measured window: the
    profiler starts before it, and a helper thread holds the
    ``bench.window`` span from the window's start for ``seconds`` (or
    until the window ends, if sooner), then stops the profiler. So the
    traced part is the first ``seconds`` of the same window an
    untraced run measures, however fast the program becomes."""

    def __init__(self, directory: Optional[str]):
        self.directory = directory
        self.active = False

    @contextlib.contextmanager
    def window(self, seconds: float):
        if self.directory is None:
            yield
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: cheap
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        done = threading.Event()

        def hold():
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                self.active = True
                done.wait(seconds)
                self.active = False
            jax.profiler.stop_trace()

        helper = threading.Thread(target=hold, name="bench-tracer")
        helper.start()
        try:
            yield
        finally:
            done.set()
            helper.join()

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def load(self) -> "Trace":
        paths = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no trace under {self.directory}")
        return Trace.from_file(paths[-1])


Interval = Tuple[float, float]


def union_ns(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint cover of the intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def module_name(event_name: str) -> str:
    """``jit_plant_batch(123)`` -> ``jit_plant_batch``."""
    return re.sub(r"\(\d+\)$", "", event_name)


class Trace:
    """A reduced profiler trace: the window, every device's module and
    op events, and the host's events, all in nanoseconds."""

    def __init__(self, window: Interval,
                 modules: List[List[Tuple[str, float, float]]],
                 ops: List[List[Tuple[str, float, float]]],
                 host: List[Tuple[str, float, float]]):
        self.window = window
        self.modules = modules      # per device: (name, start, end)
        self.ops = ops              # per device: (name, start, end)
        self.host = host            # (name, start, end)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        modules, ops, host = [], [], []
        window = None
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                mods, os_ = [], []
                for line in plane.lines:
                    dest = (mods if line.name == MODULE_LINE else
                            os_ if line.name == OP_LINE else None)
                    if dest is None:
                        continue
                    for e in line.events:
                        dest.append((e.name, e.start_ns, e.end_ns))
                if mods or os_:
                    modules.append(mods)
                    ops.append(os_)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == WINDOW_SPAN:
                            window = (e.start_ns, e.end_ns)
                        elif e.duration_ns > 0:
                            host.append((e.name, e.start_ns, e.end_ns))
        if window is None:
            raise ValueError(f"{path}: no {WINDOW_SPAN!r} span")
        return cls(window, modules, ops, host)

    # ------------------------------------------------------ clipping

    def _clip(self, lo: float, hi: float) -> float:
        return max(0.0, min(hi, self.window[1]) - max(lo, self.window[0]))

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        if not self.has_device():
            return 0.0
        per = []
        for ops in self.ops:
            cover = union_ns((lo, hi) for _, lo, hi in ops)
            per.append(sum(self._clip(lo, hi) for lo, hi in cover))
        return sum(per) / len(per) * 1e-9

    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    # ------------------------------------------------------- modules

    def _launches(self, names: Iterable[str]):
        """Launches of the named modules in the window; a name that
        ends in ``*`` matches every module it begins."""
        exact = {n for n in names if not n.endswith("*")}
        prefix = tuple(n[:-1] for n in names if n.endswith("*"))
        for mods in self.modules:
            for name, lo, hi in mods:
                key = module_name(name)
                if ((key in exact or (prefix and key.startswith(prefix)))
                        and self._clip(lo, hi) > 0):
                    yield lo, hi

    def has_device(self) -> bool:
        return any(self.ops)

    def module_launches(self, names: Iterable[str]) -> int:
        return sum(1 for _ in self._launches(names))

    def launch_s(self, names: Iterable[str]) -> List[float]:
        """Device seconds of each launch of the named modules that lies
        wholly inside the window (one cut by its edge would read
        short)."""
        lo_w, hi_w = self.window
        return [(hi - lo) * 1e-9 for lo, hi in self._launches(names)
                if lo >= lo_w and hi <= hi_w]

    def steady(self, names: Iterable[str]) -> "Trace":
        """This trace with its window starting at the first launch of
        the named modules: the part of the window past the set-up that
        precedes the first of them (the same window when none is)."""
        starts = [lo for lo, _ in self._launches(names)]
        if not starts:
            return self
        lo = max(self.window[0], min(starts))
        return Trace((lo, self.window[1]), self.modules, self.ops,
                     self.host)

    def module_names(self) -> Dict[str, float]:
        """Device seconds of every module in the window, by name."""
        out: Dict[str, float] = {}
        for mods in self.modules:
            for name, lo, hi in mods:
                key = module_name(name)
                out[key] = out.get(key, 0.0) + self._clip(lo, hi) * 1e-9
        return out

    # ----------------------------------------------------- breakdown

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` ops that ran longest in the window, by their HLO
        name and result shape (``%fusion.14 = s32[256,40000]``)."""
        agg: Dict[str, float] = {}
        for ops in self.ops:
            for name, lo, hi in ops:
                name = name.split("{")[0][:80].strip()
                agg[name] = agg.get(name, 0.0) + self._clip(lo, hi) * 1e-9
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[name, s] for name, s in top if s > 0]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest stretches of the window in which device 0
        ran no op, each named by the innermost host event around its
        middle (``host idle`` where none is)."""
        if not self.has_device():
            return []
        lo_w, hi_w = self.window
        cover = union_ns((max(lo, lo_w), min(hi, hi_w))
                         for _, lo, hi in self.ops[0] if hi > lo_w
                         and lo < hi_w)
        gaps, t = [], lo_w
        for lo, hi in cover:
            if lo > t:
                gaps.append((t, lo))
            t = max(t, hi)
        if hi_w > t:
            gaps.append((t, hi_w))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
        out = []
        for lo, hi in gaps:
            mid = (lo + hi) / 2
            around = [(e_hi - e_lo, name) for name, e_lo, e_hi in self.host
                      if e_lo <= mid <= e_hi and name not in _NOT_A_CAUSE]
            cause = min(around)[1] if around else "host idle"
            out.append([cause, (hi - lo) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
