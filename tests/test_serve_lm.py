"""Autoregressive LM serving path (PR 9).

* **KV-cache differential** — prefill-then-N-decode-steps through the
  LmEngine's jitted (donating) callables equals the full-sequence
  forward, parametrized over seq buckets and batch pow2 cells, plus a
  ring-cache case that decodes past the sliding window.
* **Engine cells** — pow2 bucketing of runner cells, the resident
  decode pool's position wrap, and the phase-aware plane factory.
* **Plane integration** — phase-keyed runner cache, LRU eviction
  accounting, compile-ahead warm-up, and the dispatcher's decode-step
  continuation hook (a completed step re-enqueues until exhaustion).
"""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.knapsack import (InstanceGroup, PackratConfig,
                                 next_power_of_two)
from repro.core.profiler import ProfileSpec, phase_profiles
from repro.models.lm import apply_head
from repro.models.serve_lm import (LM_MODELS, LmEngine, PHASE_DECODE,
                                   PHASE_PREFILL, PHASES, lm_tiny_config,
                                   make_lm_engine)
from repro.serving import (EventLoop, RealPlane, Request, SimulatedPlane,
                           TabulatedBackend, WorkerInstance, make_policy)
from repro.serving.dispatcher import Dispatcher, DispatcherConfig

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="module")
def engine():
    # max_seq 96 > the reduced gemma3 sliding window so the ring-cache
    # decode path is reachable from the differential test
    return LmEngine(max_seq=96)


# --------------------------------------------------------------------- #
# KV-cache differential: prefill + N decode steps == full forward
# --------------------------------------------------------------------- #
def _full_logits(engine, tokens):
    h = engine.model.forward(engine.params, {"tokens": tokens})
    return apply_head(engine.params, h, engine.cfg)


def _prefill_then_decode(engine, tokens, n_pre):
    """Max relative error of the incremental path vs the full forward."""
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[1]
    full = _full_logits(engine, tokens)
    scale = float(jnp.max(jnp.abs(full))) + 1e-6
    logits_last, cache = engine.prefill(tokens[:, :n_pre])
    errs = [float(jnp.max(jnp.abs(logits_last[:, 0] - full[:, n_pre - 1])))]
    for i in range(n_pre, S):
        logits, cache = engine.decode_step(cache, tokens[:, i:i + 1],
                                           jnp.int32(i))
        errs.append(float(jnp.max(jnp.abs(logits[:, 0] - full[:, i]))))
    return max(errs) / scale


@pytest.mark.parametrize("b,n_pre", [
    (1, 8), (2, 8),             # smallest seq bucket
    (1, 16), (4, 16),           # default serving bucket
    (2, 32),                    # largest pow2 bucket below the window
])
def test_prefill_decode_matches_full_forward(engine, b, n_pre):
    n_dec = 4
    tokens = jax.random.randint(jax.random.PRNGKey(b * 100 + n_pre),
                                (b, n_pre + n_dec), 0,
                                engine.cfg.vocab_size)
    assert _prefill_then_decode(engine, tokens, n_pre) < 2e-4


def test_decode_past_sliding_window_stays_faithful(engine):
    """The ring cache keeps decode exact once positions wrap the window."""
    window = engine.cfg.sliding_window
    assert window and window < engine.max_seq
    n_pre, S = window + 8, window + 16       # steps cross the wrap point
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, S), 0,
                                engine.cfg.vocab_size)
    assert _prefill_then_decode(engine, tokens, n_pre) < 2e-4


def test_lm_tiny_config_serves_through_pallas():
    cfg = lm_tiny_config()
    assert cfg.use_pallas_kernels
    assert cfg.name == "lm-tiny"
    no_kernels = cfg.with_overrides(use_pallas_kernels=False)
    with pytest.raises(ValueError, match="use_pallas_kernels"):
        LmEngine(no_kernels)


# --------------------------------------------------------------------- #
# runner cells: pow2 bucketing, resident pool, phase-aware factory
# --------------------------------------------------------------------- #
def test_prefill_runner_cells_bucket_pow2(engine):
    assert engine.prefill_runner(1, 3) is engine.prefill_runner(2, 4)
    assert engine.prefill_runner(1, 4) is not engine.prefill_runner(1, 8)
    # seq buckets key distinct cells too
    assert engine.prefill_runner(1, 4, 8) is not engine.prefill_runner(1, 4, 16)


def test_decode_runner_pool_advances_and_wraps(engine):
    run = engine.decode_runner(1, 2)
    s0 = engine.default_seq_bucket
    _, pos0 = engine._resident[2]
    for _ in range(2 * (engine.max_seq - s0)):
        run()
        _, pos = engine._resident[2]
        assert s0 <= pos < engine.max_seq
    assert engine.decode_runner(4, 2) is run      # t does not key the cell


def test_factory_routes_phases(engine):
    make = engine.factory()
    assert getattr(make, "phase_aware", False)
    assert make(1, 2, PHASE_PREFILL) is engine.prefill_runner(1, 2)
    assert make(1, 2, PHASE_DECODE) is engine.decode_runner(1, 2)
    assert make(1, 2) is engine.decode_runner(1, 2)   # default phase


def test_make_lm_engine_registry():
    assert "lm-tiny" in LM_MODELS
    assert PHASES == (PHASE_PREFILL, PHASE_DECODE)
    with pytest.raises(ValueError, match="unknown LM serving model"):
        make_lm_engine("no-such-model")


def test_gemma3_1b_engine_at_published_widths(monkeypatch):
    """gemma3-1b is served whole: published widths, all 26 layers, bf16,
    through the kernels, with a max_seq past its 512-token window (the
    engine itself is 2 GB, so its constructor is stubbed here)."""
    from repro.models import serve_lm
    monkeypatch.setattr(serve_lm, "LmEngine",
                        lambda cfg, **kw: (cfg, kw))
    cfg, kw = make_lm_engine("gemma3-1b", seed=3)
    assert "gemma3-1b" in LM_MODELS
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.vocab_size) == \
        (26, 1152, 4, 1, 256, 262_144)
    assert cfg.dtype == "bfloat16" and cfg.use_pallas_kernels
    assert kw["seed"] == 3
    assert kw["max_seq"] >= 1024 > cfg.sliding_window == 512
    # caller overrides win over the registered defaults
    assert make_lm_engine("gemma3-1b", max_seq=2048)[1]["max_seq"] == 2048


# --------------------------------------------------------------------- #
# RealPlane: phase-keyed runner cache, LRU bound, warm-up
# --------------------------------------------------------------------- #
def _phase_factory(calls):
    def make(t, b, phase=""):
        def run():
            calls[(phase, t, b)] += 1
            time.sleep(0.0002)
        return run
    make.phase_aware = True
    return make


def test_plane_runner_cache_is_phase_keyed():
    calls = collections.Counter()
    plane = RealPlane(_phase_factory(calls), total_units=2)
    a = plane.runner(1, 2, phase="prefill")
    b = plane.runner(1, 2, phase="decode")
    assert a is not b
    # partial batches round up into the pow2 cell
    c = plane.runner(1, 3, phase="prefill")
    assert c is plane.runner(1, 4, phase="prefill") and c is not a
    rep = plane.runner_report()
    assert rep["cached"] == 3 and rep["evictions"] == 0
    assert set(rep["compile_ms"]) == {"prefill:1,2", "decode:1,2",
                                      "prefill:1,4"}
    plane.close()


def test_plane_runner_lru_bound_evicts_and_counts():
    calls = collections.Counter()
    plane = RealPlane(_phase_factory(calls), total_units=2, max_runners=2)
    plane.runner(1, 1, phase="decode")
    plane.runner(1, 2, phase="decode")
    plane.runner(1, 4, phase="decode")      # evicts the (1,1) cell
    assert plane.runner_evictions == 1
    rep = plane.runner_report()
    assert rep["cached"] == 2 and rep["evictions"] == 1
    # compile_ms history survives eviction (it is an accounting record,
    # excluded from latency percentiles, not a cache)
    assert "decode:1,1" in rep["compile_ms"]
    plane.close()


def test_plane_warm_compiles_ahead_of_traffic():
    calls = collections.Counter()
    plane = RealPlane(_phase_factory(calls), total_units=2)
    warmed = plane.warm([(1, 2), (2, 4)], phase="prefill")
    assert warmed == 2
    assert plane.runner_report()["cached"] == 2
    # warm again: cells already resident, nothing new compiles
    assert plane.warm([(1, 2)], phase="prefill") == 0
    plane.close()


def test_phase_profiles_measures_each_phase():
    calls = collections.Counter()
    plane = RealPlane(_phase_factory(calls), total_units=2)
    spec = ProfileSpec(2, 2, thread_values=(1, 2))
    profs = phase_profiles(plane, spec, ("prefill", "decode"),
                           warmup=1, iters=2)
    assert set(profs) == {"prefill", "decode"}
    for phase in profs:
        assert set(profs[phase]) == set(spec.grid())
        assert all(lat > 0 for lat in profs[phase].values())
    assert calls[("prefill", 1, 1)] == 3 and calls[("decode", 1, 1)] == 3
    plane.close()


# --------------------------------------------------------------------- #
# dispatcher continuation: completed steps re-enqueue until exhaustion
# --------------------------------------------------------------------- #
def test_dispatcher_continuation_chains_decode_steps():
    profile = {(1, b): 0.010 for b in (1, 2, 4)}
    config = PackratConfig(groups=(InstanceGroup(1, 1, 2),),
                           latency=profile[(1, 2)])
    plane = SimulatedPlane(EventLoop())
    workers = [WorkerInstance(0, 1, 2, TabulatedBackend(profile))]
    responses = []
    disp = Dispatcher(plane, config, workers, responses.append,
                      DispatcherConfig(batch_timeout=0.005),
                      policy=make_policy("continuous"))

    def continue_chain(resp):
        if resp.request.steps_left > 1:
            return Request(resp.request.id + 1000,
                           plane.now,
                           phase=PHASE_DECODE,
                           steps_left=resp.request.steps_left - 1)
        return None

    disp.continuation = continue_chain
    n, steps = 3, 4
    for i in range(n):
        plane.at(0.001 * (i + 1), (lambda i=i: disp.on_request(
            Request(i, 0.001 * (i + 1), phase=PHASE_DECODE,
                    steps_left=steps))))
    plane.run_until(5.0)
    # each root request spawns steps-1 continuations
    assert len(responses) == n * steps
    chains = collections.Counter(r.request.id % 1000 for r in responses)
    assert all(v == steps for v in chains.values())


def test_dispatcher_without_continuation_is_unchanged():
    profile = {(1, b): 0.010 for b in (1, 2, 4)}
    config = PackratConfig(groups=(InstanceGroup(1, 1, 2),),
                           latency=profile[(1, 2)])
    plane = SimulatedPlane(EventLoop())
    workers = [WorkerInstance(0, 1, 2, TabulatedBackend(profile))]
    responses = []
    disp = Dispatcher(plane, config, workers, responses.append,
                      DispatcherConfig(batch_timeout=0.005),
                      policy=make_policy("continuous"))
    assert disp.continuation is None
    for i in range(4):
        plane.at(0.001 * (i + 1), (lambda i=i: disp.on_request(
            Request(i, 0.001 * (i + 1)))))
    plane.run_until(5.0)
    assert sorted(r.request.id for r in responses) == list(range(4))


# --------------------------------------------------------------------- #
# end-to-end: the LM factory behind a real plane
# --------------------------------------------------------------------- #
def test_lm_factory_serves_through_real_plane(engine):
    plane = RealPlane(engine.factory(), total_units=2)
    profile = plane.profile(ProfileSpec(2, 2, thread_values=(1, 2)),
                            warmup=0, iters=1, phase=PHASE_DECODE)
    assert all(lat > 0 for lat in profile.values())
    rep = plane.runner_report()
    assert rep["cached"] >= 1
    plane.close()


def test_request_carries_phase_fields():
    r = Request(1, 0.0, phase=PHASE_PREFILL, seq_bucket=16, steps_left=8)
    assert r.phase == PHASE_PREFILL
    assert r.seq_bucket == 16 and r.steps_left == 8
    assert Request(2, 0.0).phase == ""        # phaseless default intact


# --------------------------------------------------------------------- #
# phase-split planning: prefill and decode solved as separate cells
# (placed here rather than test_knapsack.py: that module is skipped
# wholesale when hypothesis is unavailable)
# --------------------------------------------------------------------- #
def test_phase_split_minimizes_joint_makespan():
    from repro.core import PackratOptimizer
    from repro.core.knapsack import solve_phase_split
    # prefill is 3x the cost of decode at every cell: the split must give
    # prefill the lion's share of the units
    prefill = {(t, b): 3.0 * b / t for t in (1, 2, 4) for b in (1, 2, 4)}
    decode = {(t, b): 1.0 * b / t for t in (1, 2, 4) for b in (1, 2, 4)}
    opts = {"prefill": PackratOptimizer(prefill),
            "decode": PackratOptimizer(decode)}
    split = solve_phase_split(opts, {"prefill": 4, "decode": 4}, 8)
    assert split is not None
    assert sum(split["units"].values()) == 8
    assert all(u >= 1 for u in split["units"].values())
    assert split["objective"] == pytest.approx(
        max(c.latency for c in split["configs"].values()))
    # min-max optimal: no other feasible unit partition does better
    feasible = []
    for u_pre in range(1, 8):
        c_pre = opts["prefill"].try_solve(u_pre, 4)
        c_dec = opts["decode"].try_solve(8 - u_pre, 4)
        if c_pre and c_dec:
            feasible.append(max(c_pre.latency, c_dec.latency))
    assert feasible
    assert split["objective"] == pytest.approx(min(feasible))
    # prefill is 3x slower per cell, so it can never get fewer units
    assert split["units"]["prefill"] >= split["units"]["decode"]


def test_phase_split_infeasible_returns_none():
    from repro.core import PackratOptimizer
    from repro.core.knapsack import solve_phase_split
    profile = {(2, 2): 1.0}
    opts = {"prefill": PackratOptimizer(profile),
            "decode": PackratOptimizer(profile)}
    # one unit cannot host two phase pools
    assert solve_phase_split(opts, {"prefill": 2, "decode": 2}, 1) is None
    # 3 units: one side gets 1 unit but the only item needs t=2
    assert solve_phase_split(opts, {"prefill": 2, "decode": 2}, 3) is None
    assert solve_phase_split(opts, {"prefill": 2, "decode": 2}, 4) \
        is not None


def test_phase_split_validates_inputs():
    from repro.core import PackratOptimizer
    from repro.core.knapsack import solve_phase_split
    opt = PackratOptimizer({(1, 1): 1.0})
    with pytest.raises(ValueError):
        solve_phase_split({"prefill": opt}, {"prefill": 1}, 4)
    with pytest.raises(ValueError):
        solve_phase_split({"a": opt, "b": opt}, {"a": 1, "c": 1}, 4)
    with pytest.raises(ValueError):
        solve_phase_split({"a": opt, "b": opt}, {"a": 1, "b": 1}, 4,
                          min_units=0)


# --------------------------------------------------------------------- #
# per-phase batch estimation (test_estimator.py is hypothesis-gated)
# --------------------------------------------------------------------- #
def test_phase_estimator_tracks_phases_independently():
    from repro.core.estimator import EstimatorConfig, PhaseEstimator
    est = PhaseEstimator(config=EstimatorConfig(alpha=0.5, window=4,
                                                reconfigure_timeout=0.0),
                         initial_batch=4)
    for _ in range(30):
        est.observe("prefill", 4)      # steady
        est.observe("decode", 32)      # 8x the prefill demand
    assert est.smoothed_batches() == {"prefill": 4, "decode": 32}
    changed = est.should_reconfigure(now=1.0)
    assert changed == {"decode": 32}   # only decode drifted from B=4
    est.commit(changed)
    assert est.current_batches() == {"prefill": 4, "decode": 32}
    # committed: the next check is quiet
    assert est.should_reconfigure(now=2.0) is None


def test_phase_estimator_validates_phases():
    from repro.core.estimator import PhaseEstimator
    with pytest.raises(ValueError):
        PhaseEstimator(phases=())
    est = PhaseEstimator()
    with pytest.raises(KeyError):
        est.observe("no-such-phase", 1)
