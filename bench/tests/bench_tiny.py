"""A throwaway benchmark root for the harness's CPU tests.

It holds one tiny configuration of the Qwen2 shape (d_model 32, two
layers, bfloat16, as the chip cells are served), one steady mix and the
benchmark's own metric readers, laid out as a checkout is: the harness
finds each by name from the root's ``BENCHMARK.json``.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "source": "a tiny Qwen2-shaped decoder for the harness's tests",
    "llm_config": {
        "model_type": "qwen2", "hidden_size": 32, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 64,
        "hidden_act": "silu", "num_hidden_layers": 2,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06, "vocab_size": 256,
        "tie_word_embeddings": True, "torch_dtype": "bfloat16"},
    "program": {"registered": "internvl2-1b", "overrides": {
        "d_model": 32, "n_heads": 2, "n_kv_heads": 1, "head_dim": 16,
        "d_ff": 64, "vocab_size": 256, "n_repeats": 2,
        "use_pallas_kernels": True, "attn_block_q": 16,
        "attn_block_kv": 16}},
    "serving": {"units": 2, "max_batch": 4, "initial_batch": 1,
                "max_seq": 64, "reconfigure_timeout_s": 1.0},
    # readings on the CPU over five seeds: sound runs gap at most 0.0018
    # and err at most 0.0125; the fp8 control gap 0.0165 and err 0.116
    # at least
    "check": {"limits": {"gap": 0.008, "err": 0.04}, "min_tokens": 8},
}

TINY_MIX = {"phases": [{"seconds": None, "rate_rps": 16.0}], "lead_in_s": 0.5,
            "prompt_tokens": 32, "output_tokens": 6}

CELL = "tiny.chat"
SECONDS = 2.0


def make_root(tmp: pathlib.Path, *, extra_metrics=()) -> pathlib.Path:
    """A checkout-shaped root under ``tmp`` with the tiny cell."""
    bench = tmp / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir(parents=True)
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (bench / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": CELL, "config": "tiny", "traffic": "tiny",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[CELL]) for m in real["per_layer"]
                      if m["source"] != "device_trace"]
        + list(extra_metrics),
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
