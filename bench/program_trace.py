"""The program's own spans in the run's trace, for the per-layer readers.

The served path writes ``packrat.*`` spans into the profiler's trace
(``src/repro/core/spans.py`` names them), on the clock of the device's
operations.  ``read`` keeps them from the ``.xplane.pb``, each with the
host thread it ran on and its arguments, along with the ``bench.window``
bounds and the device's busy intervals (the union of its operations).
:func:`of` hands a reader the run's :class:`ProgramTrace`, parsed once
per file and cached, or ``None``: in an untraced run, and where the
program writes no such span.  A ``ProgramTrace`` round-trips through
JSON, which is how the tests keep a small recorded one.

    python3 bench/program_trace.py [trace.xplane.pb | reduced.json]

prints where the device's idle time in the traced window went (the
newest trace under ``bench/out/trace`` by default): split by the
innermost program span open at each idle moment, on the worker threads
and on the reactor apart, and for the 10 longest idle gaps the spans
open on each thread as the gap began.
"""

from __future__ import annotations

import glob
import json
import os
import pathlib
import sys
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.tracing import DEVICE_PLANE_PREFIX, OPS_LINE, WINDOW_SPAN, union

TRACE_DIR = pathlib.Path(__file__).resolve().parent / "out" / "trace"
PREFIX = "packrat."
WORKER_SPANS = ("packrat.run",)
REACTOR_SPANS = ("packrat.dispatch", "packrat.deliver", "packrat.timer",
                 "packrat.control")
NO_SPAN = "no program span"


@dataclass
class Span:
    name: str
    thread: int        # host thread, numbered in the trace's order
    start: int         # ns, the profiler's clock
    end: int
    args: Dict[str, object]

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def label(self) -> str:
        """Short name, with the phase where the span has one."""
        short = self.name[len(PREFIX):]
        phase = self.args.get("phase")
        return f"{short}[{phase}]" if phase else short


@dataclass
class ProgramTrace:
    window_ns: Tuple[int, int]
    spans: List[Span]
    busy: List[Tuple[int, int]]     # the device's busy intervals, merged

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def began_in_window(self, name: str,
                        phase: Optional[str] = None) -> List[Span]:
        """Spans named ``name`` (of ``phase``, if given) that began
        inside the window."""
        lo, hi = self.window_ns
        return [s for s in self.spans if s.name == name
                and lo <= s.start < hi
                and (phase is None or s.args.get("phase") == phase)]

    def threads(self, names) -> set:
        """The threads that ran any span named in ``names``."""
        return {s.thread for s in self.spans if s.name in names}

    def to_json(self) -> Dict:
        return {"window_ns": list(self.window_ns),
                "spans": [[s.name, s.thread, s.start, s.end, s.args]
                          for s in self.spans],
                "busy": [list(b) for b in self.busy]}

    @classmethod
    def from_json(cls, d: Dict) -> "ProgramTrace":
        return cls(tuple(d["window_ns"]),
                   [Span(n, t, a, b, dict(args))
                    for n, t, a, b, args in d["spans"]],
                   [tuple(b) for b in d["busy"]])


def read(path: str) -> ProgramTrace:
    """The ``packrat.*`` host spans, the window and the first chip's busy
    intervals of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Span] = []
    ops: List[Tuple[int, int]] = []
    window = None
    thread = 0
    device_planes = 0
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            device_planes += 1
            if device_planes > 1:
                continue          # one-chip cells: the first chip
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((int(e.start_ns),
                                int(e.start_ns + e.duration_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append(Span(
                            e.name, thread, int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                            dict(e.stats)))
                    elif e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                thread += 1
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    spans.sort(key=lambda s: (s.start, -s.end))
    return ProgramTrace(window, spans, union(ops))


_cache: Dict[Tuple[str, int, int], ProgramTrace] = {}


def latest(directory: pathlib.Path = TRACE_DIR) -> Optional[ProgramTrace]:
    """The newest trace under ``directory``, parsed once per file."""
    files = sorted(glob.glob(str(directory / "**" / "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    st = os.stat(files[-1])
    key = (files[-1], st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = read(files[-1])
    return _cache[key]


def of(ctx) -> Optional[ProgramTrace]:
    """The run's program spans, or ``None``: an untraced run, a trace of
    another run (its window differs from the harness's reading), or a
    program that writes no span."""
    if ctx.trace is None:
        return None
    pt = latest()
    if (pt is None or not pt.spans
            or tuple(pt.window_ns) != tuple(ctx.trace.window_ns)):
        return None
    return pt


# --------------------------------------------------------------------- #
# idle attribution
# --------------------------------------------------------------------- #
def _idle(pt: ProgramTrace) -> List[Tuple[int, int]]:
    lo, hi = pt.window_ns
    gaps, t = [], lo
    for a, b in pt.busy:
        if b <= lo or a >= hi:
            continue
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _segments(spans: List[Span]):
    """``(t0, t1, labels)`` over time: ``labels`` is the sorted set of
    the innermost spans open on the given spans' threads in [t0, t1)."""
    points = sorted({s.start for s in spans} | {s.end for s in spans})
    starts = sorted(spans, key=lambda s: (s.start, -s.end))
    open_: Dict[int, List[Span]] = defaultdict(list)
    i = 0
    out = []
    for t0, t1 in zip(points, points[1:]):
        for stack in open_.values():
            stack[:] = [s for s in stack if s.end > t0]
        while i < len(starts) and starts[i].start <= t0:
            if starts[i].end > t0:
                open_[starts[i].thread].append(starts[i])
            i += 1
        labels = tuple(sorted({st[-1].label for st in open_.values() if st}))
        out.append((t0, t1, labels))
    return out


def _split(gaps, spans: List[Span]) -> Dict[str, int]:
    """Idle ns by the innermost spans open, over the gaps."""
    out: Dict[str, int] = defaultdict(int)
    total = sum(b - a for a, b in gaps)
    gi = 0
    for t0, t1, labels in _segments(spans):
        while gi < len(gaps) and gaps[gi][1] <= t0:
            gi += 1
        j = gi
        while j < len(gaps) and gaps[j][0] < t1:
            ov = min(t1, gaps[j][1]) - max(t0, gaps[j][0])
            if ov > 0 and labels:
                key = " + ".join(labels)
                out[key] += ov
                total -= ov
            j += 1
    if total:
        out[NO_SPAN] += total
    return dict(out)


def _main_label(gap, spans: List[Span]) -> str:
    """The label that covers most of one gap."""
    split = _split([gap], spans)
    return max(split, key=split.get)


def attribute(pt: ProgramTrace, top: int = 10) -> Dict:
    """The device's idle time in the window, by the innermost program span
    open on the worker threads and on the reactor (each a sorted set of
    labels where several threads hold one), and the ``top`` longest
    gaps: each thread's open spans at the gap's start, the label that
    covers most of the gap on each side, and the first dispatch from the
    gap's start on (with the reactor span it ran in, a coalescing timer
    or a delivery, and the longest wait of its requests)."""
    gaps = _idle(pt)
    workers = pt.threads(WORKER_SPANS)
    reactor = pt.threads(REACTOR_SPANS) - workers
    role = {t: "worker" for t in workers}
    role.update({t: "reactor" for t in reactor})
    side = {"workers": [s for s in pt.spans if s.thread in workers],
            "reactor": [s for s in pt.spans if s.thread in reactor]}
    out = {"window_ms": pt.window_s * 1e3,
           "idle_ms": sum(b - a for a, b in gaps) * 1e-6}
    for name, spans in side.items():
        split = _split(gaps, spans)
        out[name] = sorted(((k, v * 1e-6) for k, v in split.items()),
                           key=lambda kv: -kv[1])
    dispatches = [s for s in side["reactor"] if s.name == "packrat.dispatch"]
    longest = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        stacks = defaultdict(list)
        for s in pt.spans:
            if s.start <= a < s.end:
                stacks[s.thread].append(s.label)
        gap = {"at_ms": (a - pt.window_ns[0]) * 1e-6, "ms": (b - a) * 1e-6,
               "open": {f"{role.get(t, 'other')} {t}": " > ".join(v)
                        for t, v in sorted(stacks.items())},
               "workers": _main_label((a, b), side["workers"]),
               "reactor": _main_label((a, b), side["reactor"])}
        d = next((s for s in dispatches if s.start >= a), None)
        if d is not None:
            within = [s.label for s in side["reactor"] if s is not d
                      and s.thread == d.thread and s.start <= d.start
                      and d.end <= s.end]
            gap["next_dispatch"] = {
                "after_ms": (d.start - a) * 1e-6, "label": d.label,
                "in": " > ".join(within), "n": d.args.get("n"),
                "wait_max_ms": d.args.get("wait_max_us", 0) * 1e-3}
        longest.append(gap)
    out["longest_gaps"] = longest
    return out


def _load(path: str) -> ProgramTrace:
    if path.endswith(".json"):
        with open(path) as f:
            return ProgramTrace.from_json(json.load(f))
    return read(path)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    pt = _load(argv[0]) if argv else latest()
    if pt is None:
        print(f"no trace under {TRACE_DIR}", file=sys.stderr)
        return 1
    rep = attribute(pt)
    idle = rep["idle_ms"]
    print(f"device idle {idle:.3f} ms of a {rep['window_ms']:.3f}-ms "
          f"window ({100 * idle / rep['window_ms']:.1f}%)")
    for name in ("workers", "reactor"):
        print(f"\nidle by the innermost span open on the {name}:")
        for label, ms in rep[name]:
            print(f"  {ms:10.3f} ms {100 * ms / idle if idle else 0:5.1f}%"
                  f"  {label}")
    print("\nlongest idle gaps: ms into the window, ms; the spans open at"
          " its start; what covers most of it on the workers | reactor;"
          " the next dispatch")
    for g in rep["longest_gaps"]:
        print(f"  {g['at_ms']:9.3f} {g['ms']:8.3f}  " + "; ".join(
            f"{k}: {v}" for k, v in g["open"].items()))
        line = f"{'':20}{g['workers']} | {g['reactor']}"
        d = g.get("next_dispatch")
        if d:
            line += (f"; {d['label']} n={d['n']} in {d['in'] or '-'} after "
                     f"{d['after_ms']:.3f} ms, waited up to "
                     f"{d['wait_max_ms']:.3f} ms")
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
