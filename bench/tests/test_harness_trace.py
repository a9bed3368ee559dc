"""The reduction from trace to metrics: exact arithmetic on a hand-made
trace, and the readers on a small trace recorded on one v5e chip
(``data/trace-minitron.json``: the first 0.5 s of a traced window of the
``minitron-8b-l16.chat`` cell, reduced by ``tracing.reduce_xspace``)."""

import json
import pathlib

import pytest

import bench_tiny
from bench import model, run
from bench.tracing import Trace, covered, load, union

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _reader(name):
    return run.load_reader(name)


def test_union_and_cover():
    assert union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert covered([(0, 2), (1, 3), (5, 9)], 2, 6) == 2


def _hand_trace():
    # window 0..100 ns; a loop op holds two ops; a gap 40..60
    ops = [("%while.1", 10, 30), ("%fusion.1", 12, 10),
           ("%flash_attention.3", 25, 10), ("%fusion.2", 60, 20),
           ("%decode_attention.5", 85, 5)]
    modules = [("jit__prefill", 10, 30), ("jit__decode", 60, 35)]
    spans = [("bench.prefill", 5, 40, 4), ("bench.decode", 55, 42, 2)]
    return Trace((0, 100), ops, modules, spans)


def test_busy_idle_and_breakdown():
    tr = _hand_trace()
    assert tr.busy_s() == pytest.approx(55e-9)     # 10..40, 60..80, 85..90
    assert tr.idle_gaps() == [(0, 10), (40, 60), (80, 85), (90, 100)]
    bd = tr.breakdown()
    names = [n for n, _ in bd["device_ops"]]
    assert not any("%while" in n for n in names)
    assert names[0] == "jit__decode:%fusion.2"
    assert bd["idle_gaps"][0][0] == "bench.prefill b=4"
    assert bd["idle_gaps"][0][1] == pytest.approx(20e-9)
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10


def test_steps_take_the_batch_of_the_open_span():
    tr = _hand_trace()
    (b, secs, ops), = tr.steps("_prefill", "bench.prefill")
    assert b == 4 and secs == pytest.approx(30e-9)
    assert [o[0] for o in ops] == ["%while.1", "%fusion.1",
                                   "%flash_attention.3"]
    (b, _, ops), = tr.steps("_decode", "bench.decode")
    assert b == 2 and len(ops) == 2


def test_steps_leave_out_programs_after_the_window():
    """The profiler runs on through the drain; what ran after the
    window's span is not counted."""
    tr = _hand_trace()
    tr.modules.append(("jit__decode", 120, 30))
    tr.ops.append(("%fusion.3", 125, 20))
    tr.spans.append(("bench.decode", 118, 35, 8))
    (b, _, _), = tr.steps("_decode", "bench.decode")
    assert b == 2
    assert tr.busy_s() == pytest.approx(55e-9)


def test_json_round_trip():
    tr = _hand_trace()
    again = Trace.from_json(json.loads(json.dumps(tr.to_json())))
    assert again.busy_s() == tr.busy_s()
    assert again.breakdown() == tr.breakdown()


@pytest.fixture(scope="module")
def recorded():
    path = DATA / "trace-minitron.json"
    cfg = json.loads((bench_tiny.ROOT / "bench" / "configs"
                      / "minitron-8b-l16.json").read_text())
    meta = json.loads((DATA / "trace-minitron.meta.json").read_text())
    ref = model.family(cfg)
    ctx = run.Context(dims=ref.dims_of(cfg), ref=ref,
                      peak=model.peaks("TPU v5 lite"),
                      window_s=meta["window_s"], prompt_tokens=512,
                      decode_pos={int(b): p for b, p in
                                  meta["decode_pos"].items()},
                      trace=load(str(path)))
    return ctx


@pytest.mark.parametrize("name", ["mfu.prefill", "mfu.decode",
                                  "flash_attention_roofline",
                                  "decode_attention_roofline",
                                  "device_idle_share"])
def test_device_readers_on_a_recorded_trace(recorded, name):
    value = _reader(name)(recorded)
    assert value is not None
    assert 0.0 < value <= 100.0


def test_recorded_trace_holds_both_kernels(recorded):
    names = {n for n, _, _ in recorded.trace.ops}
    assert any("flash_attention" in n for n in names)
    assert any("decode_attention" in n for n in names)
    steps = recorded.trace.steps("_decode", "bench.decode")
    assert steps and all(b > 0 for b, _, _ in steps)


# Every reader's value on the recorded traces, as the readers gave it
# when the work counts were still one module of their own; moving them
# into the reference families changes no reading.
HELD = {
    "mfu.prefill": 62.05782458241431,
    "mfu.prefill.tpot": 62.05782458241431,
    "mfu.decode": 65.56885316627361,
    "flash_attention_roofline": 11.91059669241089,
    "flash_attention_roofline.tpot": 11.91059669241089,
    "decode_attention_roofline": 84.74946954428893,
    "device_idle_share": 10.00470739999999,
}
HELD_PROGRAM = {
    "decode_queue_wait_ms": 7.800513066666666,
    "unit_gate_wait_ms": 0.003631531034482758,
    "completion_lag_ms": 0.12523413013698628,
    "decode_lock_wait_ms": 1.5500031102362204,
    "decode_enqueue_ms": 1.5512789999999999,
    "gc_pause_share": 0.037883,
    "gc_pause_share.tpot": 0.037883,
}


@pytest.mark.parametrize("name", sorted(HELD))
def test_device_readers_hold_their_values(recorded, name):
    assert _reader(name)(recorded) == HELD[name]


@pytest.mark.parametrize("name", sorted(HELD_PROGRAM))
def test_program_readers_hold_their_values(monkeypatch, name):
    from bench import program_trace
    from bench.program_trace import ProgramTrace
    with open(DATA / "trace-internvl2-program.json") as f:
        pt = ProgramTrace.from_json(json.load(f))
    monkeypatch.setattr(program_trace, "latest", lambda: pt)
    ctx = run.Context(dims=None, peak=None, window_s=10.0, prompt_tokens=0,
                      trace=Trace(tuple(pt.window_ns)))
    assert _reader(name)(ctx) == HELD_PROGRAM[name]
