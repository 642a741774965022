"""``correct`` at a size a CPU test can hold: a sound run of the tiny
serving cell passes, the float8 control in the program's place fails, and
each fault planted under the timed path turns ``correct`` false through
the whole run."""
import argparse
import importlib.util
import json
import os
from unittest import mock

import pytest

import tiny
from bench import run as harness
from bench.lib import spec

#: limits of the tiny serving cell, from one wave on seeds 0-9 (CPU,
#: interpret-mode kernels): the mean gap read at most 8e-6 for the program
#: in bfloat16 and at least 1.03e-4 for the float8 control; the worst row's
#: mean gap at most 3.2e-5 and at least 4.1e-4
TINY_GAP = {"mean_logit_gap": {"limit": 4e-5},
            "worst_row_logit_gap": {"limit": 1.5e-4}}
SEED = 2


def _run(cell, limits, seconds=6.0):
    import jax
    args = argparse.Namespace(workload=cell.name, seed=SEED, seconds=seconds,
                              trace=0)
    v5e = spec.peaks("TPU v5 lite")
    with mock.patch.object(spec, "peaks", lambda kind: v5e):
        return harness.run(args, lambda n: jax.devices()[:n], cell,
                           limits)[0]


def test_sound_serve_run_is_correct():
    out = _run(tiny.serve_cell(), TINY_GAP)
    assert out["correct"], out["checks"]
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name
    assert set(out["checks"]) == {"failed_requests", "engine_events",
                                  "mean_logit_gap", "worst_row_logit_gap"}
    assert set(out["metrics"]) == {"setup_s", "tok_s", "itl_p95_ms"}
    assert list(out)[:6] == ["correct", "attempted", "failed", "metrics",
                             "device", "checks"]


@pytest.mark.parametrize("seed", [SEED, 9])
def test_float8_control_fails_the_serve_limit(seed):
    ctx = tiny.context(tiny.serve_cell(), TINY_GAP, seed=seed,
                       seconds=0.001)
    ctx.control = True
    out = spec.load_module("drivers", "serve").run(ctx)
    assert not out.correct
    checks = {c.name: c for c in out.checks}
    assert not checks["mean_logit_gap"].ok
    assert not checks["worst_row_logit_gap"].ok
    # the program's own reading of the same window stays beside it
    assert out.notes["program"]["mean"] <= 4e-5
    assert checks["mean_logit_gap"].value == out.notes["control"]["mean"]


def _altered_tokens(decode_step):
    def step(self, *a, **kw):
        logits, cache, st = decode_step(self, *a, **kw)
        best = logits.argmax(-1)
        return logits.at[:, 0].set(logits.max(-1) + 1.0) \
            .at[jnp.arange(len(best)), best].add(-1.0), cache, st
    import jax.numpy as jnp
    return step


def _one_row_altered(decode_step):
    def step(self, *a, **kw):
        logits, cache, st = decode_step(self, *a, **kw)
        second = jnp.argsort(logits[0])[-2]
        return logits.at[0, second].set(logits[0].max() + 1.0), cache, st
    import jax.numpy as jnp
    return step


def _state_unchanged(decode_step):
    def step(self, params, cache, *a, **kw):
        logits, _, st = decode_step(self, params, cache, *a, **kw)
        return logits, cache, st
    return step


@pytest.mark.parametrize("fault", [_altered_tokens, _one_row_altered,
                                   _state_unchanged])
def test_serve_fault_under_the_timed_path_is_caught(fault):
    from repro.models.model import Model
    with mock.patch.object(Model, "decode_step",
                           fault(Model.decode_step)):
        out = _run(tiny.serve_cell(), TINY_GAP)
    assert not out["correct"], out["checks"]
    worst = out["checks"]["worst_row_logit_gap"]
    assert worst["value"] > worst["limit"]


def test_a_window_short_of_its_samples_fails():
    cell = tiny.serve_cell(dict(min_samples={"itl_gaps": 10 ** 6}))
    with pytest.raises(RuntimeError, match="itl_gaps") as e:
        tiny.run(cell, TINY_GAP, seed=SEED, seconds=0.001)
    assert type(e.value).__name__ == "TooFewSamples"


def test_calibration_reads_the_control_as_not_correct(capsys):
    import jax
    path = os.path.join(spec.BENCH, "calibrate.py")
    mod = importlib.util.module_from_spec(
        importlib.util.spec_from_file_location("bench_calibrate", path))
    mod.__spec__.loader.exec_module(mod)
    cell = tiny.serve_cell()
    cell.limits = lambda: TINY_GAP
    with mock.patch.object(spec, "load_cell", lambda name: cell), \
            mock.patch.object(mod.chip, "require_tpu",
                              lambda n: jax.devices()[:n]):
        mod.main(["--workload", "tiny.serve", "--seeds", f"{SEED},9",
                  "--control-seeds", f"{SEED}"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    ctl, sound, summary = lines
    assert ctl["control"] and not ctl["correct_at_the_cells_limits"]
    assert sound["correct_at_the_cells_limits"]
    got = summary["numbers"]["mean_logit_gap"]
    assert got["lower"] <= 4e-5 < got["upper"]
    assert summary["seeds"] == 2 and summary["control_seeds"] == 1
