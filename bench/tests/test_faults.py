"""A run whose timed path is broken underneath comes out not correct.

Each test drives the whole of a run except the look for a chip
(``harness.cell.run_cell`` on the CPU, at the tiny size of
``data/tiny.json``: interpret-mode kernels, 8 steps) with one fault
planted in the served path, and requires ``correct`` to read false:

- a denoising step that returns its latent unchanged (positions still
  advance, so requests finish on their initial noise);
- half of the pool's slots left out of the chunk update;
- the answer altered where it is produced (one channel of each finished
  sample negated on its way out of the engine).

The exchange between chips is not a fault these cells can have: every
cell runs on one chip. A sound run of the same size is correct.
"""
import json
import os
import time

import jax.numpy as jnp
import pytest

from harness import cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2 ** 35 + 99
TRAFFIC = {"loop": "closed", "steps": 8, "guidance": 1.5}


def run(seed=SEED, control=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        tiny = json.load(f)
    return cell.run_cell(bench, {"name": "tiny.saturated", "chips": 1}, tiny,
                         TRAFFIC, seed, 2.0, False, time.monotonic(),
                         control=control)


def test_a_sound_run_is_correct():
    out = run()
    assert out["correct"], out["checks"]
    assert out["checks"]["sample_rel_l2"]["requests"] >= 2
    assert out["metrics"]["images_per_s"]["value"] > 0


def _state_unchanged(orig):
    def chunk(*a, **k):
        x, pos, bad = orig(*a, **k)
        return a[3], pos, bad
    return chunk


def _half_batch(orig):
    def chunk(*a, **k):
        x, pos, bad = orig(*a, **k)
        h = x.shape[0] // 2
        return jnp.concatenate([x[:h], a[3][h:]]), pos, bad
    return chunk


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_chunk_update_is_not_correct(monkeypatch, fault):
    from repro.serving import engine
    monkeypatch.setattr(engine, "ddpm_chunk_slots",
                        fault(engine.ddpm_chunk_slots))
    out = run()
    assert not out["correct"], out["checks"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving.engine import AsyncServeEngine
    orig = AsyncServeEngine._finish

    def finish(self, rec, status, sample, error=None):
        if sample is not None:
            sample = sample.copy()
            sample[..., 0] *= -1
        return orig(self, rec, status, sample, error)
    monkeypatch.setattr(AsyncServeEngine, "_finish", finish)
    out = run()
    assert not out["correct"], out["checks"]


def test_an_open_loop_run_times_every_request_from_when_it_was_due(
        monkeypatch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        tiny = json.load(f)
    tr = {"loop": "open", "rate_per_s": 10.0, "lead_s": 0.5, "wait_s": 30.0,
          "steps": 8, "guidance": 1.5}
    seen = {}
    orig = cell.compare

    def keep(run, *args):
        seen["run"] = run
        return orig(run, *args)
    monkeypatch.setattr(cell, "compare", keep)
    out = cell.run_cell(bench, {"name": "tiny.open", "chips": 1}, tiny, tr,
                        SEED, 2.0, False, time.monotonic())
    run = seen["run"]
    due = run.window_requests()
    assert len(due) == 20 and out["attempted"] == 20
    assert out["failed"] == 0 and out["correct"]
    for r in due:
        assert r.status == "OK"
        assert r.submit >= r.due - 1e-6
        assert r.admit >= r.submit and r.done > r.admit
    assert "images_per_s" not in out["metrics"]


def test_the_lower_precision_control_is_not_correct():
    """The reference fake-quantized at 4 bits, in the program's place."""
    out = run(control=4)
    assert not out["correct"], out["checks"]
