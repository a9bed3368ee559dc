"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file (published sizes, program overrides, serving
settings, check limits), the reference family it names under
``bench/references/`` (the plain reference and the work counts), the
traffic mix under ``bench/traffic/`` and, with ``--trace 1``, one
reader per per-layer metric under ``bench/metrics/``.  The run builds
the served stack (``client.py``) from the seed, profiles and warms
every runner cell, offers the mix open loop through its lead-in (set-up
ends there, with the server loaded as in steady state) and then for
``--seconds`` (the window), drains, checks what the window produced
against the plain reference (``check.py``), and prints one JSON line
last on standard output.  With ``--trace 1`` the profiler
traces the window's last ``TRACE_S`` seconds and the line carries the
per-layer metrics instead of the end-to-end ones: those from the
harness's spans and counters over the whole window, those from the
device trace over the traced part.

It refuses to run without a TPU holding the chips the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import traffic  # noqa: E402
from bench.model import family, load_file  # noqa: E402

DRAIN_S = 30.0            # bound on the drain after the window
TRACE_S = 5.0             # the profiler traces the window's last seconds
CHECK_POSITIONS = 64      # sampled decode positions, besides the last


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    ref: object               # the configuration's reference family
    mix: traffic.Mix
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"choose from {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = traffic.load_mix(w["traffic"], root / "bench" / "traffic")

    def mine(m: Dict) -> bool:
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]), w["config"], config,
                family(config, root), mix,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def keep_logs_inside() -> None:
    """The TPU runtime's logs go inside the checkout, not to a fixed
    path under /tmp.  Call before JAX starts."""
    logs = BENCH / "out" / "tpu_logs"
    logs.mkdir(parents=True, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(logs))


def device_check(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def configure_cache() -> str:
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    # cache every program, however quick to compile, so that only a
    # checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles: List[float] = []


def listen_for_compiles() -> None:
    """Note the time of every program JAX compiles (or loads from the
    persistent cache) in this process."""
    import jax
    if getattr(listen_for_compiles, "on", False):
        return

    def note(event, secs, **kw):
        if event == COMPILE_EVENT:
            _compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(note)
    listen_for_compiles.on = True


def weight_seed(seed: int) -> int:
    """The engine's seed: ``PRNGKey`` holds 32 bits."""
    return int(seed) % (2 ** 31 - 1)


def decode_positions(seed: int, prompt: int, max_seq: int) -> List[int]:
    """Sampled decode positions to compare, with the last position."""
    rng = np.random.default_rng([seed, 0x51])
    n = min(CHECK_POSITIONS, max_seq - prompt - 1)
    pos = rng.choice(np.arange(prompt, max_seq - 1), size=n, replace=False)
    return sorted(int(p) for p in pos) + [max_seq - 1]


def load_reader(name: str, root: pathlib.Path = ROOT):
    return load_file(root / "bench" / "metrics" / f"{name}.py",
                     f"bench_metric_{name}").read


@dataclass
class Context:
    """What a per-layer metric reader may read."""
    dims: object                      # the family's dims_of(config)
    peak: Optional[Dict[str, float]]  # flops, bytes_per_s; None off the chip
    window_s: float
    prompt_tokens: int
    ref: object = None                # the reference family: work counts
    spans: List = field(default_factory=list)      # plane time, seconds
    decode_pos: Dict[int, List[int]] = field(default_factory=dict)
    compiles_in_window: List[float] = field(default_factory=list)
    reconfigs_in_window: int = 0
    decode_steps_done: int = 0
    requests: Dict[str, object] = field(default_factory=dict)  # e2e.summarize
    trace: Optional[object] = None    # trace.Trace


@dataclass
class SetUp:
    """The engine with its cells built, the tap and the span factory."""
    cfgfile: Dict
    settings: object
    dims: object
    wseed: int
    engine: object
    tap: object
    spans: object


def set_up(cell: Cell, seed: int, fault=None) -> SetUp:
    """Weights on the device from the seed, every runner cell built."""
    from bench import model
    from bench.client import ServingSettings, SpanFactory
    from bench.tap import Tap
    from repro.models.serve_lm import LmEngine
    cfgfile, mix = cell.config, cell.mix
    sv = cfgfile["serving"]
    settings = ServingSettings(
        units=int(sv["units"]), max_batch=int(sv["max_batch"]),
        initial_batch=int(sv["initial_batch"]),
        reconfigure_timeout_s=float(sv["reconfigure_timeout_s"]))
    wseed = weight_seed(seed)
    engine = LmEngine(model.program_config(cfgfile, cell.ref), seed=wseed,
                      max_seq=int(sv["max_seq"]),
                      default_seq_bucket=mix.prompt_tokens)
    if fault is not None:
        fault(engine)
    tap = Tap(engine, seed=seed, max_batch=settings.max_batch,
              decode_positions=decode_positions(
                  seed, engine.default_seq_bucket, engine.max_seq))
    tap.build_cells(settings.max_batch)
    spans = SpanFactory(engine.factory(), time.perf_counter)
    return SetUp(cfgfile, settings, cell.ref.dims_of(cfgfile), wseed, engine,
                 tap, spans)


def run_cell(name: str, seed: int, seconds: float, trace_on: bool, *,
             root: pathlib.Path = ROOT, require_chip: bool = True,
             t_start: float = T_PROCESS, control: bool = False,
             fault=None, log=print) -> Dict:
    """One run; returns the result line's object (``check`` last).

    ``t_start`` is when set-up began (the process's start).  ``control``
    adds the fp8 control's numbers on the same rows (for setting the
    limits; the benchmark's runs leave it off).  ``require_chip=False``
    and ``fault`` are for the harness's own tests on the CPU: ``fault``
    is called with the engine before any runner cell exists, to break
    the timed path underneath."""
    cell = load_cell(name, root)
    devs = device_check(cell.chips) if require_chip else None
    import jax
    if require_chip:
        configure_cache()
    listen_for_compiles()
    from bench import model, tracing
    from bench.check import check, gather, verdict
    from bench.check import control as control_numbers
    from bench.client import build_stack, drive, lateness_ms, profile_plane
    from bench.e2e import summarize
    from repro.serving import RealPlane

    su = set_up(cell, seed, fault=fault)
    cfgfile, mix, settings, dims = su.cfgfile, cell.mix, su.settings, su.dims
    engine, tap, spans, wseed = su.engine, su.tap, su.spans, su.wseed
    plane = RealPlane(spans, settings.units, max_runners=256)
    profiles = profile_plane(plane, settings)
    stack = build_stack(plane, profiles, settings, mix.output_tokens)
    arrivals = traffic.arrivals(mix, seconds, seed)
    lead = mix.lead_in_s
    tracer = tracing.Tracer(root / "bench" / "out" / "trace") \
        if trace_on else None

    # what the window's readings start from, taken as it opens
    at_open: Dict[str, object] = {}
    at_close: Dict[str, object] = {}

    def window_opened() -> None:
        at_open["logs"] = {p: len(s.reconfig_log)
                           for p, s in stack.servers.items()}
        at_open["spans"] = len(spans.spans)
        at_open["pos"] = {b: len(v) for b, v in tap.decode_pos.items()}
        tap.recording = True
        at_open["t"] = time.perf_counter()

    def window_closed() -> None:
        tap.recording = False
        if tracer is not None:
            tracer.close_window()
        at_close["t"] = time.perf_counter()
        at_close["reconfigs"] = [
            (t - lead, p, str(cfg)) for p, s in stack.servers.items()
            for t, _, cfg in s.reconfig_log[at_open["logs"][p]:]]

    # ---------------- lead-in, then the window -------------------------
    timers = [] if tracer is None \
        else [(lead + max(0.0, seconds - TRACE_S), tracer.start)]
    drain_end = drive(stack, arrivals, decode_steps=mix.output_tokens,
                      prompt_bucket=engine.default_seq_bucket,
                      window_s=seconds, drain_s=DRAIN_S, lead_s=lead,
                      on_window_open=window_opened,
                      on_window_end=window_closed, timers=timers)
    if tracer is not None:
        tracer.stop()
    plane.close()
    t_open = at_open["t"]
    setup_s = t_open - t_start

    # ---------------- readings -----------------------------------------
    memory_peak = 0
    if devs is not None:
        memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs[:cell.chips])
    counted = stack.window_records()
    summary = summarize(counted, decode_steps=mix.output_tokens,
                        window_s=seconds, drain_end=drain_end,
                        tokens_in_window=stack.tokens_in_window)
    late = lateness_ms(stack.records)
    if late:
        log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
            f"p99 {np.percentile(late, 99):.3f} max {late[-1]:.3f} "
            f"over {len(late)} arrivals", file=sys.stderr)
    log(f"setup_s {setup_s:.3f} (lead-in {lead:g} s of it); unit split "
        f"{stack.unit_split}; attempted {summary['attempted']} failed "
        f"{summary['failed']}", file=sys.stderr)
    slow = sorted(counted, key=lambda r: -((r.first or drain_end) - r.due))
    log("slowest first tokens (due s in the window, ttft ms): " + ", ".join(
        f"{r.due - lead:.2f}:{((r.first or drain_end) - r.due) * 1e3:.0f}"
        for r in slow[:5]), file=sys.stderr)
    log("plan changes in the window (s, pool, plan): " + "; ".join(
        f"{t:.2f} {p} {c}" for t, p, c in at_close["reconfigs"]),
        file=sys.stderr)

    ctx = None
    if trace_on:
        ctx = Context(
            dims=dims, ref=cell.ref,
            peak=model.peaks(devs[0].device_kind) if devs else None,
            window_s=seconds, prompt_tokens=engine.default_seq_bucket,
            spans=[s.shifted(-t_open)
                   for s in spans.spans[at_open["spans"]:]],
            decode_pos={b: v[at_open["pos"].get(b, 0):]
                        for b, v in tap.decode_pos.items()},
            compiles_in_window=[t - t_open for t in _compiles
                                if t_open <= t <= at_close["t"]],
            reconfigs_in_window=len(at_close["reconfigs"]),
            decode_steps_done=stack.decode_steps_in_window,
            requests=summary,
            trace=tracer.read() if tracer is not None else None)

    # ---------------- the check ----------------------------------------
    tap.to_host()
    rows = gather(tap.samples, tap.prompts, engine.default_seq_bucket)
    del su, engine, tap, stack, plane, spans, profiles
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    numbers = check(rows, wseed, cfgfile, cell.ref)
    limits = cfgfile["check"]["limits"]
    correct, lines = verdict(numbers, limits,
                             int(cfgfile["check"]["min_tokens"]))

    # ---------------- the line -----------------------------------------
    metrics: Dict[str, Dict] = {}
    if trace_on:
        for m in cell.per_layer:
            value = load_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else summary[m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform if devs else "cpu",
              "kind": devs[0].device_kind if devs else "cpu",
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    out: Dict = {"correct": bool(correct),
                 "attempted": summary["attempted"],
                 "failed": summary["failed"], "metrics": metrics,
                 "device": device}
    if ctx is not None and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = ctx.trace.breakdown()
    if control:
        out["control"] = control_numbers(rows, wseed, cfgfile, cell.ref)
    out["check"] = {k: {"value": v, "limit": lim} for k, v, lim in lines}
    for k, v, lim in lines:
        rel = "at least" if k == "tokens" else "limit"
        note = f"{rel} {lim!r}" if lim is not None else "not compared"
        log(f"check {k} {v!r} ({note})", file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    keep_logs_inside()
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
