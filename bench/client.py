"""The served stack the window drives, and the open-loop client.

The stack is the program's Packrat LM serving path, assembled here from
public names only (listed in ``PERF.md`` as the benchmark's contract with
the program): one :class:`RealPlane` over an :class:`LmEngine`'s runner
factory, per-phase ``L[t,b]`` tables from ``phase_profiles`` through
that plane, a unit split from ``solve_phase_split``, and two
:class:`PackratServer` pools (prefill, decode) whose dispatchers hand a
request from prefill to decode and re-enqueue its decode steps.  This is
what ``launch/bench_serving.run_lm_policy`` builds for its ``packrat``
policy; the client only adds its own clock readings and spans.

Every runner call is a harness span: a ``TraceAnnotation`` named
``bench.<phase>`` in the profiler's trace, and a ``(phase, b, t0, t1)``
record on the client's clock.  Requests are timed from when they were
due; the client reads its clock in the dispatchers' continuation hooks.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.knapsack import (PackratOptimizer, powers_of_two,
                                 solve_phase_split)
from repro.core.profiler import (ProfileCalibrator, ProfileSpec,
                                 phase_profiles)
from repro.models.serve_lm import PHASE_DECODE, PHASE_PREFILL, PHASES
from repro.serving import (CalibratedBackend, ControllerConfig,
                           PackratServer, RealPlane, TabulatedBackend)
from repro.serving.simulator import Request


@dataclass
class Span:
    phase: str
    b: int
    t0: float
    t1: float

    def shifted(self, dt: float) -> "Span":
        return Span(self.phase, self.b, self.t0 + dt, self.t1 + dt)


@dataclass
class RequestRecord:
    due: float
    fired: Optional[float] = None       # when the arrival timer ran
    first: Optional[float] = None       # prefill delivered: first token
    last: Optional[float] = None        # last decode step delivered
    steps: int = 0                      # decode steps delivered


class SpanFactory:
    """Wraps the engine's phase-aware runner factory so that every
    runner call is a span on the client's clock and in the trace."""

    def __init__(self, make, clock: Callable[[], float]) -> None:
        self._make = make
        self._clock = clock
        self.spans: List[Span] = []
        self.phase_aware = True

    def __call__(self, t: int, b: int, phase: str = PHASE_DECODE):
        run = self._make(t, b, phase)
        name = f"bench.{phase}"
        spans, clock = self.spans, self._clock

        def span_run() -> None:
            t0 = clock()
            with jax.profiler.TraceAnnotation(name, b=b):
                run()
            spans.append(Span(phase, b, t0, clock()))

        return span_run


@dataclass
class ServingSettings:
    units: int
    max_batch: int
    initial_batch: int
    reconfigure_timeout_s: float
    profile_warmup: int = 1
    profile_iters: int = 3


def profile_plane(plane: RealPlane, s: ServingSettings):
    """Per-phase ``L[t,b]`` tables through ``plane``'s own runner cells
    (a pow2 thread axis that always holds ``units``), as the program's
    ``run_lm_scenario`` measures them.  This compiles every cell the
    window can use."""
    thread_values = tuple(sorted(set(powers_of_two(s.units)) | {s.units}))
    return phase_profiles(
        plane, ProfileSpec(s.units, s.max_batch, thread_values=thread_values),
        PHASES, warmup=s.profile_warmup, iters=s.profile_iters)


@dataclass
class Stack:
    plane: RealPlane
    servers: Dict[str, PackratServer]
    unit_split: Dict[str, int]
    records: Dict[int, RequestRecord] = field(default_factory=dict)
    tokens_in_window: int = 0
    decode_steps_in_window: int = 0
    window_start: float = 0.0
    window_end: float = math.inf

    def window_records(self) -> List[RequestRecord]:
        """The requests that arrived in the window: the ones counted."""
        return [r for r in self.records.values()
                if r.due >= self.window_start]


def build_stack(plane: RealPlane, profiles, s: ServingSettings,
                decode_steps: int) -> Stack:
    """Two Packrat pools over ``plane``, planned as ``run_lm_policy``'s
    ``packrat`` policy plans them."""
    b0 = max(1, min(s.initial_batch, s.max_batch))
    opts = {p: PackratOptimizer(profiles[p]) for p in PHASES}
    split = None
    b_dec = min(b0 * decode_steps, s.units * s.max_batch)
    while split is None and b_dec >= b0:
        split = solve_phase_split(
            opts, {PHASE_PREFILL: b0, PHASE_DECODE: b_dec}, s.units)
        if split is None:
            b_dec //= 2
    if split is None:
        raise ValueError(f"no feasible phase split of {s.units} units")
    unit_split = dict(split["units"])
    step_s = {p: profiles[p][(s.units, 1)] for p in PHASES}
    batch_timeout = max(0.002, 4.0 * max(step_s.values()))
    servers: Dict[str, PackratServer] = {}
    for p in PHASES:
        ccfg = ControllerConfig()
        ccfg.dispatch_policy = "continuous"
        ccfg.dispatcher.batch_timeout = batch_timeout
        ccfg.estimator.reconfigure_timeout = s.reconfigure_timeout_s
        ccfg.estimator.max_batch = s.max_batch
        cal = ProfileCalibrator(opts[p].profile,
                                refresh_interval=s.reconfigure_timeout_s)
        servers[p] = PackratServer(
            plane, total_units=unit_split[p], optimizer=opts[p],
            backend=CalibratedBackend(TabulatedBackend(opts[p].profile), cal),
            initial_batch=b0, config=ccfg, calibrator=cal, model_id=p,
            on_plan_apply=(lambda cfg, p=p: plane.warm(
                [(g.t, g.b) for g in cfg.groups], p)))
    return Stack(plane, servers, unit_split)


def drive(stack: Stack, arrivals: Sequence[float], *, decode_steps: int,
          prompt_bucket: int, window_s: float, drain_s: float,
          lead_s: float = 0.0,
          on_window_open: Optional[Callable[[], None]] = None,
          on_window_end: Optional[Callable[[], None]] = None,
          timers: Sequence[Tuple[float, Callable[[], None]]] = ()) -> float:
    """Offer ``arrivals`` open loop: the lead-in's until plane time
    ``lead_s``, where ``on_window_open`` is called, then the window's
    until ``lead_s + window_s`` (with ``timers`` firing at their plane
    times), where ``on_window_end`` is called; then drain until every
    request has finished or ``drain_s`` has passed.  Returns the plane
    time at which the drain ended."""
    plane, servers, recs = stack.plane, stack.servers, stack.records
    stack.window_start = lead_s
    stack.window_end = lead_s + window_s
    rid = itertools.count(1 << 40)
    origin: Dict[int, int] = {}

    def in_window() -> bool:
        return stack.window_start <= plane.now < stack.window_end

    def decode_req(i: int, steps_left: int) -> Request:
        r = Request(next(rid), plane.now, model_id=PHASE_DECODE,
                    phase=PHASE_DECODE, steps_left=steps_left)
        origin[r.id] = i
        return r

    def prefill_done(resp) -> None:
        i = resp.request.id
        recs[i].first = plane.now
        stack.tokens_in_window += in_window()
        if decode_steps > 0:
            servers[PHASE_DECODE].submit(decode_req(i, decode_steps))
        return None

    def decode_done(resp) -> Optional[Request]:
        i = origin.pop(resp.request.id)
        rec = recs[i]
        rec.steps += 1
        rec.last = plane.now
        if in_window():
            stack.decode_steps_in_window += 1
            stack.tokens_in_window += 1
        if resp.request.steps_left > 1:
            return decode_req(i, resp.request.steps_left - 1)
        return None

    servers[PHASE_PREFILL].dispatcher.continuation = prefill_done
    servers[PHASE_DECODE].dispatcher.continuation = decode_done

    def fire(i: int, req: Request) -> None:
        recs[i].fired = plane.now
        servers[PHASE_PREFILL].submit(req)

    for t, fn in timers:
        plane.at(t, fn)
    for i, t in enumerate(arrivals):
        recs[i] = RequestRecord(due=t)
        req = Request(i, t, model_id=PHASE_PREFILL, phase=PHASE_PREFILL,
                      seq_bucket=prompt_bucket)
        plane.at(t, lambda i=i, req=req: fire(i, req))
    plane.run_until(lead_s)
    if on_window_open is not None:
        on_window_open()
    plane.run_until(stack.window_end)
    if on_window_end is not None:
        on_window_end()

    def done() -> bool:
        return all(r.steps >= decode_steps and r.first is not None
                   for r in recs.values())

    deadline = stack.window_end + drain_s
    while not done() and plane.now < deadline:
        plane.run_until(min(deadline, plane.now + 0.25))
    return plane.now


def lateness_ms(records: Dict[int, RequestRecord]) -> List[float]:
    """How late each arrival timer ran after its due time."""
    return sorted((r.fired - r.due) * 1e3 for r in records.values()
                  if r.fired is not None)


__all__ = ["RequestRecord", "ServingSettings", "Span", "SpanFactory",
           "Stack", "build_stack", "drive", "lateness_ms", "profile_plane"]
