"""A new model family is files only: a reference family under
``bench/references/``, a configuration that names it, a mix and the
``BENCHMARK.json`` entries.  The stub family here reads its published
keys from a group of its own, which the dense family cannot read, and
counts attention in one layer of two, as a hybrid would; the harness
and the readers find it by name and call nothing else."""

import json
import pathlib
import sys
import time

import pytest

import bench_tiny
from bench import model, run
from bench.tracing import load

DATA = pathlib.Path(__file__).resolve().parent / "data"

STUB = '''"""A stub family: the dense family's reference and work counts, its
sizes from a ``stub_config`` group, attention in one layer of two."""

import pathlib

from bench import model
from bench.work import Work

_dense = model.family({"reference": "dense"},
                      pathlib.Path(__file__).resolve().parents[2])
CALLS = []


def _published(cfgfile):
    return {"llm_config": cfgfile["stub_config"]}


def dims_of(cfgfile):
    CALLS.append("dims_of")
    return _dense.dims_of(_published(cfgfile))


def check_program(cfgfile, cfg):
    CALLS.append("check_program")
    _dense.check_program(_published(cfgfile), cfg)


def logits_at(seed, cfgfile, sequences, positions, *, precision="f32"):
    CALLS.append("logits_at:" + precision)
    return _dense.logits_at(seed, _published(cfgfile), sequences, positions,
                            precision=precision)


def _half(w):
    return Work(w.flops / 2, w.bytes / 2)


def prefill(dims, b, s):
    return _half(_dense.prefill(dims, b, s))


def decode(dims, b, pos):
    return _half(_dense.decode(dims, b, pos))


def flash_attention(dims, b, s):
    return _half(_dense.flash_attention(dims, b, s))


def decode_attention(dims, b, pos):
    return _half(_dense.decode_attention(dims, b, pos))
'''

WORK_READERS = ["mfu.prefill", "mfu.decode", "flash_attention_roofline",
                "decode_attention_roofline"]


def _stub_config(published):
    """A configuration file in the stub's form: the published keys under
    ``stub_config``, and the stub named as its reference."""
    cfg = {k: v for k, v in published.items() if k != "llm_config"}
    cfg["stub_config"] = published.get("llm_config", published)
    cfg["reference"] = "stub"
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("family"))
    (root / "bench" / "references" / "stub.py").write_text(STUB)
    cfg = _stub_config(bench_tiny.TINY_CONFIG)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    return root


def test_stub_family_sets_up_and_checks_a_cell(root):
    """``set_up`` checks the program and reads the sizes through the
    stub, and ``check`` and the control compute their logits there."""
    cell = run.load_cell(bench_tiny.CELL, root)
    assert cell.ref.__file__ == str(root / "bench" / "references" / "stub.py")
    out = run.run_cell(bench_tiny.CELL, 5, bench_tiny.SECONDS, False,
                       root=root, require_chip=False,
                       t_start=time.perf_counter(), control=True,
                       log=lambda *a, **k: None)
    assert out["correct"] is True
    assert out["check"]["tokens"]["value"] >= 8
    assert out["control"]["err"] > out["check"]["err"]["value"]
    assert sys.modules["bench_reference_stub"].CALLS == [
        "check_program", "dims_of", "logits_at:f32", "logits_at:f32",
        "logits_at:fp8"]


def test_stub_family_counts_the_work_readers_read(root):
    """On a recorded trace each work reader reads through ``ctx.ref``:
    the stub's halved counts give half the dense family's share."""
    mini = json.loads((bench_tiny.ROOT / "bench" / "configs"
                       / "minitron-8b-l16.json").read_text())
    meta = json.loads((DATA / "trace-minitron.meta.json").read_text())
    trace = load(str(DATA / "trace-minitron.json"))

    def ctx(cfg):
        ref = model.family(cfg, root)
        return run.Context(dims=ref.dims_of(cfg), ref=ref,
                           peak=model.peaks("TPU v5 lite"),
                           window_s=meta["window_s"], prompt_tokens=512,
                           decode_pos={int(b): p for b, p in
                                       meta["decode_pos"].items()},
                           trace=trace)

    dense, stub = ctx(mini), ctx(_stub_config(mini))
    for name in WORK_READERS:
        reader = run.load_reader(name, root)
        assert reader(stub) == pytest.approx(reader(dense) / 2, rel=1e-12)
