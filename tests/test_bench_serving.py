"""Scenario registry + end-to-end benchmark CLI tests (ISSUE 1)."""

import json

import pytest

from repro.core import PackratOptimizer
from repro.core.paper_profiles import RESNET50
from repro.launch import bench_serving
from repro.serving.scenarios import (ScenarioContext, get_scenario,
                                     list_scenarios, register_scenario)
from repro.serving.workloads import PoissonWorkload, TraceWorkload

EXPECTED_SCENARIOS = {"steady-poisson", "bursty", "choppy", "diurnal",
                      "step-up", "step-down", "ramp", "flash-crowd",
                      "overload", "flash-overload", "node-failure"}


def small_ctx(duration=12.0, units=8, seed=0):
    opt = PackratOptimizer(RESNET50.profile(units, 128))
    return ScenarioContext(threads=units, optimizer=opt, duration=duration,
                           seed=seed)


# --------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------- #
def test_builtin_scenarios_registered():
    names = {sc.name for sc in list_scenarios()}
    assert EXPECTED_SCENARIOS <= names


def test_get_unknown_scenario_raises():
    with pytest.raises(KeyError, match="unknown scenario"):
        get_scenario("no-such-scenario")


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        register_scenario("steady-poisson", "dup",
                          lambda ctx: PoissonWorkload(rate_rps=1.0))


def test_scenario_builders_produce_workloads():
    ctx = small_ctx()
    for sc in list_scenarios():
        wl = sc.build(ctx)
        times = wl.arrivals(ctx.duration, seed=ctx.seed)
        assert times == sorted(times)
        assert all(0 <= t < ctx.duration for t in times)
        assert times, f"scenario {sc.name} generated no load"


def test_capacity_rps_matches_optimizer():
    ctx = small_ctx()
    cfg = ctx.optimizer.solve(8, 16)
    assert ctx.capacity_rps(16) == pytest.approx(16 / cfg.latency)


def test_flash_crowd_uses_trace_replay():
    wl = get_scenario("flash-crowd").build(small_ctx())
    assert isinstance(wl, TraceWorkload)


# --------------------------------------------------------------------- #
# end-to-end runner
# --------------------------------------------------------------------- #
RUN_KW = dict(model=RESNET50, units=8, duration=10.0, seed=0,
              initial_batch=4, max_batch=64, slo_factor=4.0,
              reconfigure_timeout=2.0)


def test_run_scenario_reports_both_policies():
    result = bench_serving.run_scenario(get_scenario("step-up"), **RUN_KW)
    assert result["offered"] > 0
    for policy in ("static", "packrat"):
        rep = result[policy]
        assert rep["latency_ms"]["p50"] is not None
        assert rep["latency_ms"]["p99"] is not None
        assert rep["goodput_rps"] >= 0
        assert "reconfigurations" in rep
    assert result["static"]["reconfigurations"] == 0
    assert result["packrat"]["reconfigurations"] >= 1


def test_run_scenario_is_deterministic():
    a = bench_serving.run_scenario(get_scenario("bursty"), **RUN_KW)
    b = bench_serving.run_scenario(get_scenario("bursty"), **RUN_KW)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_run_scenario_dispatch_axis():
    """dispatches=("sync", "continuous") adds +continuous report keys
    (sync keeps the bare policy names) and stays deterministic."""
    kw = dict(RUN_KW, dispatches=("sync", "continuous"))
    a = bench_serving.run_scenario(get_scenario("bursty"), **kw)
    assert a["policies"] == ["static", "static+continuous",
                             "packrat", "packrat+continuous"]
    for key in a["policies"]:
        rep = a[key]
        assert rep["latency_ms"]["p95"] is not None
        assert rep["dispatch"] == ("continuous" if "+" in key else "sync")
        assert rep["instances"], f"no per-instance stats for {key}"
    # the sync keys are the same runs the single-axis report produces
    sync_only = bench_serving.run_scenario(get_scenario("bursty"), **RUN_KW)
    for key in ("static", "packrat"):
        assert (json.dumps(a[key], sort_keys=True)
                == json.dumps(sync_only[key], sort_keys=True))
    b = bench_serving.run_scenario(get_scenario("bursty"), **kw)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_static_policy_uses_fat_config_only():
    result = bench_serving.run_scenario(get_scenario("diurnal"), **RUN_KW)
    assert result["static"]["final_config"].startswith("[<1,8,")


def test_cli_writes_json_report(tmp_path):
    out = tmp_path / "report.json"
    rc = bench_serving.main([
        "--scenario", "step-up", "--model", "resnet50", "--units", "8",
        "--duration", "8", "--initial-batch", "4", "--max-batch", "64",
        "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    # every report leads with the schema version so downstream consumers
    # detect format changes instead of silently misparsing (ISSUE 5)
    assert report["schema_version"] == bench_serving.SCHEMA_VERSION
    assert report["model"] == "resnet50"
    sc = report["scenarios"]["step-up"]
    for policy in ("static", "packrat"):
        assert sc[policy]["latency_ms"]["p99"] is not None
        assert "goodput_rps" in sc[policy]
        assert "reconfigurations" in sc[policy]


def test_cli_trace_replay(tmp_path):
    trace = TraceWorkload.record(PoissonWorkload(rate_rps=6.0), 8.0, seed=1)
    path = tmp_path / "trace.json"
    trace.save_json(path)
    out = tmp_path / "report.json"
    rc = bench_serving.main([
        "--trace", str(path), "--model", "resnet50", "--units", "8",
        "--duration", "8", "--initial-batch", "4", "--max-batch", "64",
        "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    (name, sc), = report["scenarios"].items()
    assert name.startswith("trace:")
    assert sc["offered"] == len(trace.times)


def test_cli_real_execution_smoke(tmp_path):
    """bench_serving --execution real: short wall-clock trace on a micro
    model end-to-end — wall-clock-measured latencies and a populated
    expected-vs-observed calibration section (acceptance criterion)."""
    pytest.importorskip("jax")
    out = tmp_path / "real.json"
    rc = bench_serving.main([
        "--scenario", "steady-poisson", "--units", "2", "--duration", "1",
        "--initial-batch", "2", "--max-batch", "8", "--dispatch", "sync",
        "--execution", "real", "--real-model", "mlp-tiny",
        "--real-rate-cap", "150", "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["schema_version"] == bench_serving.SCHEMA_VERSION
    assert report["execution"] == "real"
    sc = report["scenarios"]["steady-poisson"]
    assert sc["execution"] == "real" and sc["real_model"] == "mlp-tiny"
    assert sc["measured_profile_ms"]
    assert all(v > 0 for v in sc["measured_profile_ms"].values())
    for key in ("static", "packrat"):
        rep = sc[key]
        assert rep["completed"] > 0
        assert rep["latency_ms"]["p95"] is not None
        assert rep["latency_ms"]["p95"] > 0          # wall-clock measured
        cal = rep["calibration"]
        assert cal["observations"] > 0 and cal["entries"]
        assert cal["global_ratio"] > 0


def test_cli_real_execution_rejects_sim_only_flags():
    pytest.importorskip("jax")       # the registry check imports micro models
    with pytest.raises(SystemExit):
        bench_serving.main(["--execution", "real", "--models",
                            "resnet50,bert"])
    with pytest.raises(SystemExit):
        bench_serving.main(["--execution", "real", "--interference"])
    with pytest.raises(SystemExit):
        bench_serving.main(["--execution", "real", "--model", "resnet50"])
    with pytest.raises(SystemExit):
        bench_serving.main(["--execution", "real",
                            "--real-model", "no-such-model"])


def test_cli_list(capsys):
    assert bench_serving.main(["--list"]) == 0
    listed = capsys.readouterr().out
    for name in EXPECTED_SCENARIOS | EXPECTED_MM_SCENARIOS:
        assert name in listed


# --------------------------------------------------------------------- #
# multi-model resource plane (ISSUE 3)
# --------------------------------------------------------------------- #
from repro.core.paper_profiles import BERT, PAPER_MODELS  # noqa: E402
from repro.serving.scenarios import (get_mm_scenario,     # noqa: E402
                                     list_mm_scenarios)

EXPECTED_MM_SCENARIOS = {"mixed-steady", "mixed-diurnal", "mixed-burst"}

MM_KW = dict(models={"resnet50": RESNET50, "bert": BERT}, units=8,
             duration=10.0, seed=0, initial_batch=4, max_batch=64,
             slo_factor=4.0, reconfigure_timeout=2.0)


def test_builtin_mm_scenarios_registered():
    assert EXPECTED_MM_SCENARIOS <= {sc.name for sc in list_mm_scenarios()}


def test_mm_scenarios_build_per_model_workloads():
    from repro.serving.scenarios import (MultiModelScenarioContext,
                                         ScenarioContext)
    from repro.core import PackratOptimizer
    contexts = {
        name: ScenarioContext(
            threads=4, optimizer=PackratOptimizer(pm.profile(4, 64)),
            duration=12.0, seed=0)
        for name, pm in (("resnet50", RESNET50), ("bert", BERT))}
    mctx = MultiModelScenarioContext(models=("resnet50", "bert"),
                                     contexts=contexts, duration=12.0)
    for sc in list_mm_scenarios():
        workloads = sc.build(mctx)
        assert set(workloads) == {"resnet50", "bert"}
        for name, wl in workloads.items():
            times = wl.arrivals(12.0, seed=3)
            assert times and times == sorted(times)


def test_run_mm_scenario_reports_per_model_and_aggregate():
    result = bench_serving.run_mm_scenario(
        get_mm_scenario("mixed-steady"), **MM_KW)
    assert result["models"] == ["resnet50", "bert"]
    assert result["even_shares"] == {"resnet50": 4, "bert": 4}
    for policy in ("static", "packrat"):
        rep = result[policy]
        assert set(rep["models"]) == {"resnet50", "bert"}
        for name, sub in rep["models"].items():
            for q in ("p50", "p95", "p99"):
                assert sub["latency_ms"][q] is not None, (policy, name, q)
            assert sub["goodput_rps"] >= 0
        assert rep["worst_model_p95_ms"] == pytest.approx(
            max(sub["latency_ms"]["p95"] for sub in rep["models"].values()))
        assert set(rep["tenants"]) == {"resnet50", "bert"}
        assert set(rep["shares"]) == {"resnet50", "bert"}
        # leases stay within the pool
        assert sum(rep["shares"].values()) <= 8
    assert result["static"]["plans"] == 0
    # every worker row is tagged with its tenant
    tags = {row["model_id"] for row in result["packrat"]["instances"]}
    assert tags == {"resnet50", "bert"}


def test_run_mm_scenario_is_deterministic():
    a = bench_serving.run_mm_scenario(get_mm_scenario("mixed-burst"),
                                      **MM_KW)
    b = bench_serving.run_mm_scenario(get_mm_scenario("mixed-burst"),
                                      **MM_KW)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_mm_dispatch_axis_keys():
    kw = dict(MM_KW, dispatches=("sync", "continuous"), duration=8.0)
    a = bench_serving.run_mm_scenario(get_mm_scenario("mixed-steady"), **kw)
    assert a["policies"] == ["static", "static+continuous",
                             "packrat", "packrat+continuous"]
    for key in a["policies"]:
        assert a[key]["dispatch"] == ("continuous" if "+" in key else "sync")
        assert a[key]["worst_model_p95_ms"] is not None


def test_packrat_multimodel_beats_static_even_split_worst_p95():
    """ISSUE 3 acceptance: on the anti-correlated two-model mix with
    identical seeded traces, the live resource plane's worst-tenant p95
    beats the static even split's, and per-model p50/p95/p99 + goodput
    are all reported."""
    result = bench_serving.run_mm_scenario(
        get_mm_scenario("mixed-diurnal"), **dict(MM_KW, duration=15.0))
    static = result["static"]
    packrat = result["packrat"]
    assert packrat["worst_model_p95_ms"] < static["worst_model_p95_ms"]
    assert packrat["plans"] >= 1                # the planner actually ran
    for rep in (static, packrat):
        for sub in rep["models"].values():
            assert sub["latency_ms"]["p50"] is not None
            assert sub["latency_ms"]["p95"] is not None
            assert sub["latency_ms"]["p99"] is not None
            assert "goodput_rps" in sub


def test_cli_multimodel_writes_report(tmp_path):
    out = tmp_path / "mm.json"
    rc = bench_serving.main([
        "--models", "resnet50,bert", "--scenario", "mixed-steady",
        "--units", "8", "--duration", "8", "--initial-batch", "4",
        "--max-batch", "64", "--dispatch", "sync", "--out", str(out)])
    assert rc == 0
    report = json.loads(out.read_text())
    assert report["schema_version"] == bench_serving.SCHEMA_VERSION
    assert report["models"] == ["resnet50", "bert"]
    sc = report["scenarios"]["mixed-steady"]
    for policy in ("static", "packrat"):
        assert set(sc[policy]["models"]) == {"resnet50", "bert"}


def test_parse_models_duplicates_become_tenants():
    models = bench_serving._parse_models("bert,bert")
    assert list(models) == ["bert", "bert#2"]
    with pytest.raises(ValueError):
        bench_serving._parse_models("bert")
    with pytest.raises(ValueError):
        bench_serving._parse_models("bert,doesnotexist")


# --------------------------------------------------------------------- #
# --interference and --slo-ms satellites
# --------------------------------------------------------------------- #
def test_interference_flag_slows_observed_latency():
    """Fig. 9 expected-vs-observed gap: with the CPU interference model
    the same trace reports higher p50 than the isolated profile run,
    while the optimizer's expected latency is unchanged."""
    clean = bench_serving.run_scenario(get_scenario("steady-poisson"),
                                       **RUN_KW)
    noisy = bench_serving.run_scenario(get_scenario("steady-poisson"),
                                       **RUN_KW, interference=True)
    for policy in ("static", "packrat"):
        assert noisy[policy]["interference"] is True
        assert clean[policy]["interference"] is False
        assert (noisy[policy]["latency_ms"]["p50"]
                > clean[policy]["latency_ms"]["p50"])
    # deterministic under the flag too
    again = bench_serving.run_scenario(get_scenario("steady-poisson"),
                                       **RUN_KW, interference=True)
    assert (json.dumps(noisy, sort_keys=True)
            == json.dumps(again, sort_keys=True))


def test_slo_ms_reports_largest_feasible_batch():
    from repro.core import PackratOptimizer
    result = bench_serving.run_scenario(get_scenario("steady-poisson"),
                                        **RUN_KW, slo_ms=400.0)
    assert result["slo_deadline_ms"] == pytest.approx(400.0)
    feas = result["slo_feasible"]["resnet50"]
    assert feas is not None
    assert feas["latency_ms"] <= 400.0
    # the next power-of-two batch must violate the SLO
    opt = PackratOptimizer(RESNET50.profile(8, 64))
    nxt = opt.solve(8, feas["batch"] * 2)
    assert nxt.latency * 1e3 > 400.0


def test_slo_ms_infeasible_reports_none():
    result = bench_serving.run_scenario(get_scenario("steady-poisson"),
                                        **RUN_KW, slo_ms=0.001)
    assert result["slo_feasible"]["resnet50"] is None


def test_mm_slo_ms_per_model_feasible_batch():
    result = bench_serving.run_mm_scenario(
        get_mm_scenario("mixed-steady"), **dict(MM_KW, duration=8.0),
        slo_ms=500.0)
    feas = result["slo_feasible"]
    assert set(feas) == {"resnet50", "bert"}
    for name, sub in feas.items():
        assert sub is not None and sub["latency_ms"] <= 500.0


# --------------------------------------------------------------------- #
# persistent compilation cache placement
# --------------------------------------------------------------------- #
def test_compile_cache_defers_to_env_else_fixed_checkout_path(monkeypatch):
    import jax
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/cache")
        assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None   # untouched
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        got = compile_cache.configure_compile_cache()
        root = compile_cache.DEFAULT_CACHE_DIR.parent
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert (root / "src" / "repro").is_dir()     # inside the checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
