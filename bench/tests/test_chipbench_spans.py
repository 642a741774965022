"""The readers of the serving engine's spans, on a trace made up by hand
and on the spans of a real tiny serving run on the CPU."""
import pytest

import tiny
from bench import run as harness
from bench.lib import chip, spec
from bench.lib import trace as T

READERS = ("engine.host_syncs_per_step", "engine.commit_idle",
           "model_step.prefill_ms", "model_step.decode_busy_ms")


class View:
    def __init__(self, record, reduced):
        self.record, self.reduced = record, reduced


def read(name, record, reduced):
    return spec.load_module("metrics", name).read(View(record, reduced))


#: one wave in a 1000 ns window: a prefill, two steps that commit and
#: decode, and a last step that only commits (a wave out of cache)
WAVE = [("engine.wave", 100, 800), ("engine.prefill", 100, 200),
        ("engine.sync", 250, 40),
        ("engine.step", 300, 200), ("engine.commit", 300, 100),
        ("engine.sync", 310, 20), ("engine.sync", 340, 20),
        ("engine.decode", 400, 100), ("engine.sync", 450, 30),
        ("engine.step", 500, 200), ("engine.commit", 500, 60),
        ("engine.sync", 510, 20),
        ("engine.decode", 560, 140), ("engine.sync", 600, 50),
        ("engine.step", 700, 200), ("engine.commit", 700, 50),
        ("engine.sync", 710, 10)]
#: busy 120-250, 320-380, 410-490, 520-620, 720-730, 950-1000 (cut)
OPS = [("fusion.1", 120, 130), ("fusion.2", 320, 60), ("copy.3", 410, 80),
       ("fusion.4", 520, 100), ("fusion.5", 720, 10), ("copy.6", 950, 100)]
#: a warm-up wave before the window, and the runtime's own events
OUTSIDE = [("engine.prefill", -500, 200), ("engine.step", -300, 100),
           ("engine.sync", -250, 10), ("np.asarray", 310, 20),
           ("bench.wave", 100, 800)]


def made_up(host=WAVE, decode_calls=2, waves=1):
    tr = T.Trace({0: OPS}, [("bench.window", 0, 1000)] + OUTSIDE + host)
    record = {"waves": [dict(decode_calls=decode_calls)]
              + [dict(decode_calls=0)] * (waves - 1)}
    return record, T.reduce(tr, 1)


def test_syncs_per_step_by_hand():
    # two rows and a poison read, one row and a poison read, one row;
    # the prefill's sync and the warm-up's lie outside the window's steps
    assert read("engine.host_syncs_per_step", *made_up()) == \
        pytest.approx(6 / 3)


def test_commit_idle_by_hand():
    # idle inside the commits: 300-320 and 380-400; 500-520; 700-720 and
    # 730-750, of a 1000 ns window
    assert read("engine.commit_idle", *made_up()) == pytest.approx(10.0)


def test_prefill_ms_by_hand():
    # the warm-up's prefill starts before the window and is left out
    assert read("model_step.prefill_ms", *made_up()) == \
        pytest.approx(200e-6)


def test_decode_busy_ms_by_hand():
    # busy inside the steps: 60 + 80 + 100 + 10 ns over three steps
    assert read("model_step.decode_busy_ms", *made_up()) == \
        pytest.approx(250e-6 / 3)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_engine_spans_reads_nothing(name):
    record, reduced = made_up(host=[])
    assert read(name, record, reduced) is None
    assert read(name, record, None) is None


@pytest.mark.parametrize("name,span", [
    ("engine.host_syncs_per_step", "engine.step"),
    ("engine.host_syncs_per_step", "engine.sync"),
    ("engine.commit_idle", "engine.commit"),
    ("model_step.decode_busy_ms", "engine.step")])
def test_a_renamed_span_fails_with_its_name(name, span):
    host = [(n.replace(span, "engine.renamed"), s, d) for n, s, d in WAVE]
    with pytest.raises(RuntimeError, match=span):
        read(name, *made_up(host=host))


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("span,kw", [("engine.prefill", dict(waves=2)),
                                     ("engine.decode",
                                      dict(decode_calls=3))])
def test_counts_that_differ_from_the_record_fail(name, span, kw):
    with pytest.raises(RuntimeError, match=span):
        read(name, *made_up(**kw))


def test_spans_of_a_real_run_agree_with_its_record(tmp_path):
    """A tiny serving run traced on the CPU: the engine's spans match the
    driver's waves and decode calls, and a step reads each row's token
    and the decode call's poison count."""
    cell = tiny.serve_cell()
    ctx = tiny.context(cell, {"mean_logit_gap": {"limit": 1.0},
                              "worst_row_logit_gap": {"limit": 1.0}},
                       seconds=0.001)
    ctx.trace, ctx.trace_dir = True, str(tmp_path)
    out = spec.load_module("drivers", "serve").run(ctx)
    (path,) = chip.trace_files(str(tmp_path))
    tr = T.load(path)
    tr.device = {0: []}           # the CPU has no device plane to read
    view = harness.View(cell, out, T.reduce(tr, 1), None, ctx.seconds)
    waves = out.record["waves"]
    calls = sum(w["decode_calls"] for w in waves)
    tokens = sum(len(o) for w in waves for o in w["out"])
    # no wave of the tiny mix runs out of cache: one decode call a step
    got = spec.load_module("metrics", "engine.host_syncs_per_step").read(
        view)
    assert got == pytest.approx((tokens + calls) / calls)
    assert spec.load_module("metrics", "model_step.prefill_ms").read(
        view) > 0
