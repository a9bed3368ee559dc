"""The program's spans as the per-layer readers see them: exact arithmetic
on a hand-made span set, the idle attribution, and the readers on a
small slice of a trace recorded on one v5e chip
(``data/trace-internvl2-program.json``: a traced window of the
``internvl2-1b-lm.chat`` cell, reduced by ``program_trace.read``)."""

import json
import pathlib

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import program_trace, run
from bench.program_trace import ProgramTrace, Span
from bench.tracing import Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000                      # ns
READERS = ["decode_queue_wait_ms", "unit_gate_wait_ms", "completion_lag_ms",
           "decode_lock_wait_ms", "decode_enqueue_ms", "gc_pause_share",
           "gc_pause_share.tpot"]
REACTOR, W1, W2, PREFILL = 0, 1, 2, 3


def _s(name, thread, t0, t1, **args):
    """A span from ``t0`` to ``t1`` ms."""
    return Span(name, thread, round(t0 * MS), round(t1 * MS), args)


def _hand():
    """Window 1..11 ms.  Batch 0 began before it; batch 2's step waits
    1.5 ms for the lock batch 1 holds; two collections overlap, one
    began before the window and one runs past its end."""
    d = "packrat.dispatch"
    spans = [
        _s(d, REACTOR, 0.5, 0.6, phase="decode", batch=0, n=8,
           wait_sum_us=80000, wait_max_us=10000),
        _s(d, REACTOR, 2.0, 2.1, phase="decode", batch=1, n=4,
           wait_sum_us=4000, wait_max_us=1500),
        _s(d, REACTOR, 3.0, 3.1, phase="decode", batch=2, n=2,
           wait_sum_us=1000, wait_max_us=600),
        _s(d, REACTOR, 4.0, 4.1, phase="prefill", batch=3, n=1,
           wait_sum_us=9999, wait_max_us=9999),
        _s("packrat.gate", W1, 0.6, 0.9, batch=0, units=1),
        _s("packrat.gate", W1, 2.1, 2.3, batch=1, units=1),
        _s("packrat.gate", W2, 3.1, 3.6, batch=2, units=1),
        _s("packrat.gate", PREFILL, 4.1, 4.1, batch=3, units=2),
        _s("packrat.run", W1, 0.9, 1.5, batch=0, phase="decode", b=8),
        _s("packrat.run", W1, 2.3, 5.0, batch=1, phase="decode", b=4),
        _s("packrat.run", W2, 3.6, 7.0, batch=2, phase="decode", b=2),
        _s("packrat.run", PREFILL, 4.1, 6.0, batch=3, phase="prefill", b=1),
        _s("packrat.lock", W1, 0.9, 1.2, b=8),
        _s("packrat.lock", W1, 2.3, 2.4, b=4),
        _s("packrat.lock", W2, 3.6, 5.1, b=2),
        _s("packrat.enqueue", W1, 2.4, 2.6, phase="decode", b=4),
        _s("packrat.enqueue", PREFILL, 4.1, 4.5, phase="prefill", b=1),
        _s("packrat.enqueue", W2, 5.1, 5.4, phase="decode", b=2),
        _s("packrat.enqueue", W1, 10.9, 11.5, phase="decode", b=4),
        _s("packrat.deliver", REACTOR, 1.6, 1.7, batch=0),
        _s("packrat.deliver", REACTOR, 5.4, 5.5, batch=1),
        _s("packrat.deliver", REACTOR, 6.0, 6.1, batch=3),
        _s("packrat.deliver", REACTOR, 7.1, 7.2, batch=2),
        _s("packrat.gc", REACTOR, 0.8, 1.3, gen=2, collected=0),
        _s("packrat.gc", REACTOR, 8.0, 8.6, gen=0, collected=5),
        _s("packrat.gc", W1, 8.4, 9.0, gen=1, collected=3),
        _s("packrat.gc", W2, 10.7, 11.4, gen=2, collected=9),
    ]
    spans.sort(key=lambda s: (s.start, -s.end))
    return ProgramTrace((1 * MS, 11 * MS), spans, [])


EXPECTED = {
    "decode_queue_wait_ms": (4000 + 1000) / (4 + 2) * 1e-3,
    "unit_gate_wait_ms": (0.2 + 0.5 + 0.0) / 3,
    "completion_lag_ms": (0.1 + 0.4 + 0.0 + 0.1) / 4,
    "decode_lock_wait_ms": (0.1 + 1.5) / 2,
    "decode_enqueue_ms": 0.3,
    "gc_pause_share": 100.0 * (1.0 + 0.3) / 10.0,
    "gc_pause_share.tpot": 100.0 * (1.0 + 0.3) / 10.0,
}


def _ctx(window_ns):
    return run.Context(dims=None, peak=None, window_s=10.0,
                       prompt_tokens=0, trace=Trace(tuple(window_ns)))


@pytest.mark.parametrize("name", READERS)
def test_reader_arithmetic_on_a_hand_made_span_set(monkeypatch, name):
    pt = _hand()
    monkeypatch.setattr(program_trace, "latest", lambda: pt)
    assert run.load_reader(name)(_ctx(pt.window_ns)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_program_spans(monkeypatch, name):
    """Untraced; a trace of another run; a program that writes no span
    (the parent of this change): no number, and nothing raised."""
    reader = run.load_reader(name)
    pt = _hand()
    monkeypatch.setattr(program_trace, "latest", lambda: pt)
    assert reader(run.Context(dims=None, peak=None, window_s=10.0,
                              prompt_tokens=0)) is None
    assert reader(_ctx((2 * MS, 11 * MS))) is None
    silent = ProgramTrace(pt.window_ns, [], [])
    monkeypatch.setattr(program_trace, "latest", lambda: silent)
    assert reader(_ctx(pt.window_ns)) is None
    monkeypatch.setattr(program_trace, "latest", lambda: None)
    assert reader(_ctx(pt.window_ns)) is None


def test_gc_share_is_zero_where_no_collection_ran(monkeypatch):
    pt = _hand()
    pt.spans = [s for s in pt.spans if s.name != "packrat.gc"]
    monkeypatch.setattr(program_trace, "latest", lambda: pt)
    assert run.load_reader("gc_pause_share")(_ctx(pt.window_ns)) == 0.0


def test_json_round_trip():
    pt = _hand()
    pt.busy = [(1 * MS, 2 * MS)]
    again = ProgramTrace.from_json(json.loads(json.dumps(pt.to_json())))
    assert again == pt


def test_idle_attribution_by_innermost_span():
    """One gap, 40..60 ms: one worker waits for the lock inside its run
    while the other waits for the device; the reactor delivers for 5 ms
    of it, and a coalescing timer dispatches the next batch as it ends."""
    spans = [_s("packrat.run", W1, 30, 55, batch=1, phase="decode", b=4),
             _s("packrat.lock", W1, 42, 52, b=4),
             _s("packrat.run", W2, 50, 70, batch=2, phase="decode", b=4),
             _s("packrat.device_wait", W2, 50, 70, phase="decode", b=4),
             _s("packrat.deliver", REACTOR, 45, 50, batch=0),
             _s("packrat.timer", REACTOR, 60, 61, late_us=0),
             _s("packrat.dispatch", REACTOR, 60.25, 60.75, phase="decode",
                batch=3, n=3, rid=7, wait_sum_us=60000, wait_max_us=25000)]
    spans.sort(key=lambda s: (s.start, -s.end))
    pt = ProgramTrace((0, 100 * MS), spans, [(0, 40 * MS), (60 * MS, 100 * MS)])
    rep = program_trace.attribute(pt)
    assert rep["idle_ms"] == pytest.approx(20.0)
    assert dict(rep["workers"]) == pytest.approx({
        "run[decode]": 2, "lock": 8,
        "device_wait[decode] + lock": 2,
        "device_wait[decode] + run[decode]": 3,
        "device_wait[decode]": 5})
    assert dict(rep["reactor"]) == pytest.approx({
        "deliver": 5, program_trace.NO_SPAN: 15})
    (gap,) = rep["longest_gaps"]
    assert gap["at_ms"] == pytest.approx(40) and gap["ms"] == pytest.approx(20)
    assert gap["open"] == {f"worker {W1}": "run[decode]"}
    assert gap["workers"] == "lock"
    assert gap["reactor"] == program_trace.NO_SPAN
    assert gap["next_dispatch"] == pytest.approx({
        "after_ms": 20.25, "label": "dispatch[decode]", "in": "timer",
        "n": 3, "wait_max_ms": 25.0})


@pytest.fixture(scope="module")
def recorded():
    with open(DATA / "trace-internvl2-program.json") as f:
        return ProgramTrace.from_json(json.load(f))


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_recorded_trace(monkeypatch, recorded, name):
    monkeypatch.setattr(program_trace, "latest", lambda: recorded)
    value = run.load_reader(name)(_ctx(recorded.window_ns))
    assert value is not None and value >= 0.0
    if name.startswith("gc_pause_share"):
        assert value <= 100.0
    else:
        assert value < 1000.0         # ms


def test_recorded_trace_attributes_its_idle_time(recorded):
    rep = program_trace.attribute(recorded)
    assert rep["idle_ms"] > 0
    for side in ("workers", "reactor"):
        assert sum(ms for _, ms in rep[side]) == pytest.approx(rep["idle_ms"])
    assert rep["longest_gaps"]
