"""The harness end to end on the CPU, at a tiny size: the served stack
assembled from the program's public names, the open-loop window, the
drain and the check; and a configuration, a mix and a per-layer metric
found by name from files alone."""

import json
import time

import pytest

import bench_tiny
from bench import run


def _run(root, seed=7, trace=False):
    return run.run_cell(bench_tiny.CELL, seed, bench_tiny.SECONDS, trace,
                        root=root, require_chip=False,
                        t_start=time.perf_counter(),
                        log=lambda *a, **k: None)


def test_refuses_without_a_chip():
    with pytest.raises(run.NoChip):
        run.device_check(1)


def test_tiny_cell_end_to_end(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    out = _run(root)
    from bench import traffic
    mix = traffic.load_mix("tiny", root / "bench" / "traffic")
    assert out["correct"] is True
    assert out["attempted"] == traffic.offered_count(mix, bench_tiny.SECONDS)
    assert out["failed"] == 0
    names = [m["name"] for m in json.loads(
        (root / "BENCHMARK.json").read_text())["end_to_end"]]
    assert list(out["metrics"]) == names
    for m in out["metrics"].values():
        assert m["value"] > 0
    assert list(out)[-1] == "check"
    assert out["check"]["tokens"]["value"] >= 8


def test_config_and_metric_found_by_name(tmp_path):
    """A later PR adds a configuration, a mix and a per-layer metric as
    files and entries; no harness file changes."""
    extra = {"name": "prefill_calls", "unit": "count", "better": "higher",
             "source": "program_span", "layer": "engine",
             "moves": "tpot_p50_ms", "workloads": ["tiny2.chat"]}
    root = bench_tiny.make_root(tmp_path, extra_metrics=[extra])
    (root / "bench" / "metrics" / "prefill_calls.py").write_text(
        "from bench.tracing import in_window\n\n\n"
        "def read(ctx):\n"
        "    return len(in_window(ctx.spans, ctx.window_s, 'prefill'))\n")
    cfg = dict(bench_tiny.TINY_CONFIG)
    cfg["serving"] = dict(cfg["serving"], max_batch=2)
    (root / "bench" / "configs" / "tiny2.json").write_text(json.dumps(cfg))
    mix = dict(bench_tiny.TINY_MIX, output_tokens=3)
    (root / "bench" / "traffic" / "tiny2.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny2", "source": "test",
                            "file": "bench/configs/tiny2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny2.chat", "config": "tiny2",
                              "traffic": "tiny2", "chips": 1,
                              "why": "test"})
    for m in spec["per_layer"]:
        m["workloads"] = m["workloads"] + ["tiny2.chat"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = run.run_cell("tiny2.chat", 11, bench_tiny.SECONDS, True,
                       root=root, require_chip=False,
                       t_start=time.perf_counter(),
                       log=lambda *a, **k: None)
    assert out["correct"] is True
    assert out["metrics"]["prefill_calls"]["value"] >= 1
    assert "reconfigs" in out["metrics"]
    assert out["device"]["window_s"] > 0
