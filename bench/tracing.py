"""The profiler's trace, reduced to what the per-layer metrics read.

``Tracer`` runs ``jax.profiler`` over the window (Python tracing off, so
the server's host path is not slowed by it).  ``read`` turns the
``.xplane.pb`` it writes into a :class:`Trace`: the device's operation
events and program (XLA module) events, and the harness's own spans
(``bench.prefill`` / ``bench.decode``), all on the profiler's clock.
A ``Trace`` round-trips through JSON, which is how the tests keep a
small recorded one.

Busy time is the union of the device's operation intervals; the idle
share is 1 minus busy over the traced window.  The breakdown lists the
device operations that took most time (loops that hold other operations
left out), and the longest idle gaps named by the harness span open on
the host when each began.  Operations keep their short HLO names
(``%fusion.12``); programs their jit names (``jit__decode``).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import pathlib
import shutil
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINER_OPS = ("%while", "%conditional", "%call")


# --------------------------------------------------------------------- #
# interval helpers (seconds or nanoseconds alike)
# --------------------------------------------------------------------- #
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the union of the intervals."""
    total = 0.0
    for a, b in union((max(a, lo), min(b, hi)) for a, b in intervals
                      if b > lo and a < hi):
        total += b - a
    return total


def in_window(spans, window_s: float, phase: Optional[str] = None):
    """Client spans that ended inside ``[0, window_s)``."""
    return [s for s in spans if 0.0 <= s.t1 < window_s
            and (phase is None or s.phase == phase)]


# --------------------------------------------------------------------- #
# the reduced trace
# --------------------------------------------------------------------- #
@dataclass
class Trace:
    window_ns: Tuple[int, int]
    ops: List[Tuple[str, int, int]] = field(default_factory=list)
    modules: List[Tuple[str, int, int]] = field(default_factory=list)
    spans: List[Tuple[str, int, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def busy_s(self) -> float:
        lo, hi = self.window_ns
        return covered(((s, s + d) for _, s, d in self.ops), lo, hi) * 1e-9

    def idle_gaps(self) -> List[Tuple[int, int]]:
        lo, hi = self.window_ns
        busy = union((max(s, lo), min(s + d, hi)) for _, s, d in self.ops
                     if s + d > lo and s < hi)
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def span_at(self, t: int) -> str:
        """The harness span open on the host at ``t`` (the latest to
        start), or "no runner call"."""
        best = None
        for name, s, d, b in self.spans:
            if s <= t < s + d and (best is None or s > best[1]):
                best = (name, s, b)
        return f"{best[0]} b={best[2]}" if best else "no runner call"

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        per_op: Dict[str, float] = defaultdict(float)
        lo, hi = self.window_ns
        for name, s, d in self.ops:
            if s < hi and s + d > lo and not name.startswith(CONTAINER_OPS):
                per_op[name] += d * 1e-9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[self.module_of(n), v] for n, v in ops],
                "idle_gaps": [[self.span_at(a), (b - a) * 1e-9]
                              for a, b in gaps]}

    def module_of(self, op: str) -> str:
        """``module:op`` for the breakdown, by the first run of ``op``."""
        for name, s, d in self.ops:
            if name == op:
                for m, ms, md in self.modules:
                    if ms <= s < ms + md:
                        return f"{m}:{op}"
                break
        return op

    def in_modules(self, module_pattern: str):
        """Module events that began in the window and whose name holds
        ``module_pattern``, each with the operation events that ran
        inside it."""
        lo, hi = self.window_ns
        mods = sorted((s, s + d, n) for n, s, d in self.modules
                      if module_pattern in n and lo <= s < hi)
        starts = [m[0] for m in mods]
        inside: List[List[Tuple[str, int, int]]] = [[] for _ in mods]
        for name, s, d in self.ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                inside[i].append((name, s, d))
        return [(m, ops) for m, ops in zip(mods, inside)]

    def steps(self, module_pattern: str, span_name: str):
        """Each device run of a program whose module name holds
        ``module_pattern``, as ``(b, seconds, ops inside)``: ``b`` is the
        batch of the harness span ``span_name`` open on the host when the
        run began (the latest such span to start), or 0 if none."""
        spans = sorted((s, s + d, b) for n, s, d, b in self.spans
                       if n == span_name)
        out = []
        for (m0, m1, _), ops in self.in_modules(module_pattern):
            b = 0
            for s0, s1, sb in spans:
                if s0 > m0:
                    break
                if s1 >= m0:
                    b = sb
            out.append((b, (m1 - m0) * 1e-9, ops))
        return out

    def to_json(self) -> Dict:
        return {"window_ns": list(self.window_ns), "ops": self.ops,
                "modules": self.modules, "spans": self.spans}

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls(tuple(d["window_ns"]), [tuple(x) for x in d["ops"]],
                   [tuple(x) for x in d["modules"]],
                   [tuple(x) for x in d["spans"]])


def _stat(event, key, default=None):
    for k, v in event.stats:
        if k == key:
            return v
    return default


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return hlo_text.split(" = ", 1)[0]


def reduce_xspace(path: str) -> Trace:
    """The first chip's operations and programs, and the harness's spans;
    the window is the ``bench.window`` span."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    window = None
    device_planes = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            device_planes += 1
            if device_planes > 1:
                continue          # one-chip cells: the first chip
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((short_name(e.name), int(e.start_ns),
                                int(e.duration_ns)) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name.split("(", 1)[0],
                                    int(e.start_ns), int(e.duration_ns))
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    if e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    else:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns),
                                      int(_stat(e, "b", 0) or 0)))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    return Trace(window, ops, modules, spans)


class Tracer:
    """The profiler over the last part of the window, into ``directory``
    (emptied first).  ``start`` opens the ``bench.window`` span that
    marks the traced window and ``close_window`` closes it; ``stop``
    stops the profiler.  Stopping writes the trace out, which holds the
    calling thread for seconds, so the run stops it only after the drain:
    the requests in flight when the window closes finish undisturbed,
    and what the profiler records after the span is left out."""

    def __init__(self, directory: pathlib.Path) -> None:
        self.dir = pathlib.Path(directory)
        self._span = None
        self._on = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._on = True
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def close_window(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self) -> None:
        import jax
        self.close_window()
        if self._on:
            self._on = False
            jax.profiler.stop_trace()

    def read(self) -> Trace:
        files = sorted(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return reduce_xspace(files[-1])


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))


__all__ = ["Trace", "Tracer", "covered", "in_window", "load",
           "reduce_xspace", "union"]
