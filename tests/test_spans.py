"""The served path's spans in the profiler's trace (``repro.core.spans``).

lm-tiny is served through a :class:`RealPlane` by two Packrat pools
(prefill, decode) under ``jax.profiler.trace``; the ``.xplane.pb`` is
read back with ``ProfileData``.  Every ``packrat.*`` span appears with
its arguments, the engine's spans nest inside the plane's runner call on
the worker's thread, and the spans of one batch share its ``batch``
number, from the dispatcher to the delivery on the reactor.
"""

import gc
import glob

import jax
import pytest

from repro.core import spans
from repro.launch.bench_serving import run_lm_policy
from repro.models.serve_lm import LmEngine, PHASE_DECODE, PHASES

ARGS = {
    "packrat.dispatch": {"phase", "batch", "n", "rid", "wait_sum_us",
                         "wait_max_us"},
    "packrat.gate": {"batch", "units"},
    "packrat.run": {"batch", "phase", "b", "n", "worker"},
    "packrat.lock": {"b"},
    "packrat.enqueue": {"phase", "b"},
    "packrat.device_wait": {"phase", "b"},
    "packrat.deliver": {"batch"},
    "packrat.timer": {"late_us"},
    "packrat.control": {"pool", "replanned"},
    "packrat.gc": {"gen", "collected"},
}


def _read(directory):
    """``packrat.*`` host events as (name, thread, start, end, args)."""
    from jax.profiler import ProfileData
    path, = glob.glob(str(directory / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("packrat."):
                    out.append((e.name, (plane.name, i), e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def events(tmp_path_factory):
    engine = LmEngine()
    # every runner cell compiled before the trace starts
    for b in (1, 2, 4):
        engine.prefill_runner(1, b)
        engine.decode_runner(1, b)
    # (1, b) entries far above any CPU step, so no watchdog re-dispatches;
    # (2, 1) sets the coalescing window
    profile = {(t, b): (0.005 if t == 2 else 5.0) * b
               for t in (1, 2) for b in (1, 2, 4)}
    directory = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(directory)):
        rep = run_lm_policy(
            "packrat", [0.01 * i for i in range(6)],
            factory=engine.factory(), profiles={p: profile for p in PHASES},
            units=2, duration=0.2, initial_batch=1, max_batch=4,
            decode_steps=3, slo_by_phase={p: 10.0 for p in PHASES},
            reconfigure_timeout=100.0)
        gc.collect()
    assert rep["unit_split"] == {p: 1 for p in PHASES}
    return _read(directory)


def _named(events, name):
    return [e for e in events if e[0] == name]


@pytest.mark.parametrize("name", sorted(ARGS))
def test_span_appears_with_its_arguments(events, name):
    got = _named(events, name)
    assert got, f"no {name} span"
    for _, _, t0, t1, args in got:
        assert ARGS[name] <= set(args), (name, args)
        assert t1 >= t0


@pytest.mark.parametrize("name", ["packrat.lock", "packrat.enqueue",
                                  "packrat.device_wait"])
def test_engine_spans_nest_in_the_runner_call(events, name):
    runs = _named(events, "packrat.run")
    for _, thread, t0, t1, args in _named(events, name):
        outer = [r for r in runs if r[1] == thread and r[2] <= t0
                 and t1 <= r[3]]
        assert len(outer) == 1, (name, t0)
        if "phase" in args:
            assert outer[0][4]["phase"] == args["phase"]
        assert outer[0][4]["b"] == args["b"]


def test_a_batch_keeps_its_number_from_dispatch_to_delivery(events):
    dispatched = {e[4]["batch"]: e for e in _named(events, "packrat.dispatch")}
    assert len(dispatched) == len(_named(events, "packrat.dispatch"))
    by_batch = {}
    for name in ("packrat.gate", "packrat.run", "packrat.deliver"):
        for e in _named(events, name):
            by_batch.setdefault(e[4]["batch"], {})[name] = e
    assert set(by_batch) == set(dispatched)
    for batch, d in dispatched.items():
        gate, run, deliver = (by_batch[batch][n] for n in (
            "packrat.gate", "packrat.run", "packrat.deliver"))
        assert run[4]["phase"] == d[4]["phase"]
        assert run[4]["n"] == d[4]["n"]
        assert gate[1] == run[1] != d[1]          # worker, then reactor
        assert deliver[1] == d[1]
        assert d[2] <= gate[2] <= gate[3] <= run[2] <= run[3] <= deliver[2]
    decode = [d for d in dispatched.values()
              if d[4]["phase"] == PHASE_DECODE]
    assert decode
    for _, _, _, _, a in decode:
        assert 0 <= a["wait_max_us"] <= a["wait_sum_us"] <= \
            a["n"] * a["wait_max_us"]


def test_a_collection_under_the_profiler_is_a_span(events):
    assert any(a["gen"] == 2 for *_, a in _named(events, "packrat.gc"))


def test_off_the_profiler_nothing_is_built_or_recorded(tmp_path):
    assert not spans.on()
    assert spans.span("packrat.run", batch=1) is spans.NOOP
    with spans.span("packrat.control", pool="decode") as span:
        assert span is None
    spans.watch_collector()
    spans.watch_collector()
    hooks = [cb for cb in gc.callbacks if cb is spans._COLLECTOR]
    assert len(hooks) == 1
    # a collection that began with the profiler off records nothing,
    # even if the profiler is on when it stops
    hooks[0]("start", {"generation": 0})
    with jax.profiler.trace(str(tmp_path)):
        hooks[0]("stop", {"generation": 0, "collected": 0})
    assert not _named(_read(tmp_path), "packrat.gc")
