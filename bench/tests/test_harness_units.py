"""The harness's arithmetic on the CPU: the traffic generator, the
end-to-end metrics (with an injected stall), the peaks table, the work
counts and the configuration cross-check, through the reference family
each configuration names."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import model, traffic
from bench.client import RequestRecord
from bench.e2e import nearest_rank, summarize

ROOT = bench_tiny.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("mix_name", sorted(
    {w["traffic"] for w in BENCH["workloads"]}))
@pytest.mark.parametrize("seed", [0, 12345, 2 ** 33 + 17])
def test_generator_deterministic_per_seed(mix_name, seed):
    mix = traffic.load_mix(mix_name)
    a = traffic.arrivals(mix, 30.0, seed)
    assert a == traffic.arrivals(mix, 30.0, seed)
    assert a == sorted(a) and 0 < a[0] and a[-1] < mix.lead_in_s + 30.0
    # every seed offers the same work, in the lead-in and in the window,
    # in another order
    b = traffic.arrivals(mix, 30.0, seed + 1)
    lead = mix.lead_in_s
    assert len(a) == len(b)
    assert (sum(t >= lead for t in a) == sum(t >= lead for t in b)
            == traffic.offered_count(mix, 30.0))
    assert a != b


def test_generator_rates_and_phases():
    mix = traffic.Mix("t", ((8.0, 2.0), (4.0, 6.0)), 512, 128)
    a = np.asarray(traffic.arrivals(mix, 24.0, 3))
    counts = [int(((a >= lo) & (a < hi)).sum())
              for lo, hi in ((0, 8), (8, 12), (12, 20), (20, 24))]
    assert counts == [16, 24, 16, 24]
    steady = traffic.Mix("s", ((None, 5.0),), 512, 128)
    gaps = np.diff([0.0] + traffic.arrivals(steady, 40.0, 9))
    assert len(gaps) == 200
    # exponential gaps: coefficient of variation near 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_lead_in_runs_the_cycle_before_the_window():
    mix = traffic.Mix("t", ((8.0, 2.0), (4.0, 6.0)), 512, 128, 12.0)
    a = np.asarray(traffic.arrivals(mix, 24.0, 5))
    counts = [int(((a >= lo) & (a < hi)).sum())
              for lo, hi in ((0, 8), (8, 12), (12, 20), (20, 24),
                             (24, 32), (32, 36))]
    assert counts == [16, 24, 16, 24, 16, 24]
    assert traffic.offered_count(mix, 24.0) == 80


def _token_times(n, *, steps, gap=0.01, stall_at=None, stall_s=0.0):
    """Request ``i`` is due at 0.1 i; its first token 50 ms later, then
    one every ``gap``.  A stall freezes the server for ``stall_s`` at
    ``stall_at``: every token due after it comes that much later."""
    out = []
    for i in range(n):
        due = 0.1 * i
        times = [due + 0.05 + gap * k for k in range(steps + 1)]
        if stall_at is not None:
            times = [t + stall_s if t >= stall_at else t for t in times]
        out.append((due, times))
    return out


def _summary(reqs, window, steps):
    recs = [RequestRecord(due=d, fired=d, first=t[0], last=t[-1],
                          steps=steps) for d, t in reqs]
    tokens = sum(t < window for _, ts in reqs for t in ts)
    return summarize(recs, decode_steps=steps, window_s=window,
                     drain_end=window + 5.0, tokens_in_window=tokens)


def test_metrics_show_an_injected_stall():
    window, steps = 10.0, 64
    calm = _token_times(100, steps=steps)
    a = _summary(calm, window, steps)
    b = _summary(_token_times(100, steps=steps, stall_at=5.0, stall_s=0.5),
                 window, steps)
    assert a["failed"] == b["failed"] == 0
    assert math.isclose(a["tpot_p50_ms"], 10.0, rel_tol=1e-6)
    assert math.isclose(a["tpot_p95_ms"], 10.0, rel_tol=1e-6)
    # the stall lengthens the token gaps of the requests it caught ...
    assert b["tpot_p95_ms"] == pytest.approx(10.0 + 500.0 / steps)
    assert b["tpot_p50_ms"] == pytest.approx(a["tpot_p50_ms"])
    # ... and pushes the tokens of the window's last half second out
    lost = sum(window - 0.5 <= t < window for _, ts in calm for t in ts)
    assert lost > 100
    assert b["output_tok_s"] == pytest.approx(a["output_tok_s"]
                                              - lost / window)


@pytest.mark.parametrize("name", ["ttft_p95_ms", "tpot_p95_ms"])
def test_request_tail_readers_read_the_stalled_tail(name):
    """The per-layer copies of the tails read what the end-to-end
    summary reads, stall included, and nothing where no request was."""
    from bench import run
    window, steps = 10.0, 64
    s = _summary(_token_times(100, steps=steps, stall_at=5.0, stall_s=0.5),
                 window, steps)
    ctx = run.Context(dims=None, peak=None, window_s=window,
                      prompt_tokens=32, requests=s)
    reader = run.load_reader(f"request_{name}")
    assert reader(ctx) == s[name]
    assert reader(run.Context(dims=None, peak=None, window_s=window,
                              prompt_tokens=32)) is None


def test_unfinished_requests_fail_and_miss_every_limit():
    reqs = _token_times(10, steps=8)
    recs = [RequestRecord(due=d, fired=d, first=t[0], last=t[-1], steps=8)
            for d, t in reqs]
    recs[3] = RequestRecord(due=0.3, fired=0.3, first=0.4, last=0.5,
                            steps=2)
    recs[4] = RequestRecord(due=0.4, fired=0.4)
    s = summarize(recs, decode_steps=8, window_s=5.0, drain_end=60.0,
                  tokens_in_window=0)
    assert s["attempted"] == 10 and s["failed"] == 2
    # 2 of 10 failed: the 95th percentile is a failed request's wait
    assert s["ttft_p95_ms"] == pytest.approx((60.0 - 0.4) * 1e3)
    assert s["tpot_p95_ms"] == pytest.approx((60.0 - 0.4) / 8 * 1e3)


def test_nearest_rank():
    v = sorted(range(1, 101))
    assert nearest_rank(v, 95) == 95 and nearest_rank(v, 50) == 50
    assert nearest_rank([3.0], 95) == 3.0


def test_peaks_by_device_kind():
    p = model.peaks("TPU v5 lite")
    assert p == {"flops": 197e12, "bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        model.peaks("TPU v9 imaginary")


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.fixture(scope="module")
def dense():
    return model.family({"reference": "dense"})


def test_program_configs_match_published_keys():
    cfg = _cfg("minitron-8b-l16")
    mini = model.program_config(cfg, model.family(cfg))
    assert (mini.n_heads, mini.n_kv_heads, mini.resolved_head_dim,
            mini.n_layers) == (48, 8, 128, 16)
    cfg = _cfg("internvl2-1b-lm")
    lm = model.program_config(cfg, model.family(cfg))
    assert (lm.d_model, lm.n_heads, lm.n_layers) == (896, 14, 24)
    bad = _cfg("minitron-8b-l16")
    bad["program"]["overrides"]["n_heads"] = 32      # the repo's 32
    with pytest.raises(ValueError, match="n_heads"):
        model.program_config(bad, model.family(bad))


def test_work_counts(dense):
    dm = dense.dims_of(_cfg("minitron-8b-l16"))
    per_layer = 4096 * (48 + 16) * 128 + 48 * 128 * 4096 + 2 * 4096 * 16384
    assert dense.layer_params(dm) == per_layer
    assert dense.weight_bytes(dm) == 2 * (16 * per_layer + 4096 * 256000)
    w = dense.decode(dm, 16, 767)
    # decode is bound by bytes: weights plus K/V at 768 positions
    assert w.least_s(197e12, 819e9) == w.bytes / 819e9
    assert w.bytes > dense.weight_bytes(dm) + 16 * 768 * 16 * 2 * 8 * 128 * 2
    att = dense.flash_attention(dm, 1, 4)
    assert att.flops == 4.0 * 48 * 128 * (4 * 5 // 2) * 16
    p = dense.prefill(dm, 16, 512)
    assert p.least_s(197e12, 819e9) == p.flops / 197e12


@pytest.mark.parametrize("change", [
    {"reference": "dense2"}, {"reference": "../configs/minitron-8b-l16"},
    {"n_heads": 32}, {"rope_theta": 500000.0}, {"tie_embeddings": True},
    {"d_ff": 14336}], ids=["unknown", "outside", "n_heads", "rope_theta",
                           "tie_embeddings", "d_ff"])
def test_family_refusals(change):
    """A family with no file under ``bench/references/`` is refused, with
    the path it looked for; so is a dense file whose program differs
    from its published keys in one key."""
    cfg = _cfg("minitron-8b-l16")
    if "reference" in change:
        cfg.update(change)
        with pytest.raises(FileNotFoundError, match="bench/references/"):
            model.family(cfg)
        return
    cfg["program"]["overrides"].update(change)
    (key,) = change
    with pytest.raises(ValueError, match=key):
        model.program_config(cfg, model.family(cfg))


def _whole_matrix_attention(mm, q, k, v, precision):
    """Causal attention over the whole (S, S) score matrix at once."""
    S, D = q.shape[1], q.shape[-1]
    s = mm(q, k, precision, "rqhd,rkhd->rhqk") / math.sqrt(D)
    causal = np.tril(np.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
    return mm(p, v, precision, "rhqk,rkhd->rqhd")


@pytest.mark.parametrize("precision", ["f32", "fp8"])
@pytest.mark.parametrize("block", [32, 64, 256])
def test_blocked_attention_equals_whole_matrix(dense, precision, block):
    """The reference's attention in blocks of queries is the attention of
    the whole score matrix, to float32 rounding, in the control's fp8 as
    in float32."""
    rng = np.random.default_rng(block)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 4, 16)), jnp.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(dense.attention(q, k, v, precision, block))
        want = np.asarray(_whole_matrix_attention(dense._mm, q, k, v,
                                                    precision))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_query_block_divides_the_padded_length(dense):
    assert [dense.q_block(s) for s in (128, 640, 1024, 8192)] == \
        [128, 128, 512, 512]
