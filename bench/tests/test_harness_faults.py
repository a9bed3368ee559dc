"""The check that decides ``correct``: the fp8 control in the program's
place fails it, and so does each fault the cells can have when it is
planted under the timed path (one chip: there is no exchange between
chips to leave out)."""

import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from bench import run
from bench.check import verdict


def _run(root, fault=None, control=False, seed=5):
    return run.run_cell(bench_tiny.CELL, seed, bench_tiny.SECONDS, False,
                        root=root, require_chip=False,
                        t_start=time.perf_counter(), control=control,
                        fault=fault, log=lambda *a, **k: None)


def _state_unchanged(engine):
    """A decode step that computes its logits and returns the cache it
    was given, unwritten."""
    model = engine.model

    @jax.jit
    def step(params, cache, tokens, pos):
        logits, _ = model.decode_step(params, cache, tokens, pos)
        return logits, cache

    engine.jit_decode = step


def _half_batch(engine):
    """Decode computes the first half of the batch; the second half of
    the rows get the first half's logits."""
    real = engine.jit_decode

    def step(params, cache, tokens, pos):
        logits, cache = real(params, cache, tokens, pos)
        b = logits.shape[0]
        if b > 1:
            logits = jnp.concatenate([logits[:b // 2]] * 2, axis=0)
        return logits, cache

    engine.jit_decode = step


def _answer_altered(engine):
    """The decode step's answer is altered where it is produced: one
    logit of every row is raised."""
    real = engine.jit_decode

    def step(params, cache, tokens, pos):
        logits, cache = real(params, cache, tokens, pos)
        return logits.at[:, :, 3].add(1.0), cache

    engine.jit_decode = step


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("faults"))


def test_control_fails_the_limits(root):
    out = _run(root, control=True)
    assert out["correct"] is True
    limits = bench_tiny.TINY_CONFIG["check"]["limits"]
    ok, lines = verdict(out["control"], limits, 1)
    assert not ok, lines


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_planted_fault_is_not_correct(root, fault):
    out = _run(root, fault=fault)
    assert out["correct"] is False, out["check"]
