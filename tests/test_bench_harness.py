"""Benchmark-harness plumbing: fork-pool determinism, the --quick matrix,
env-knob validation, and the compare.py regression gate.

These tests guard the CI tiers themselves: the bench-gate job is only
trustworthy if the pool fan-out is bit-deterministic, the quick subset is
what it claims to be, and the gate's pass/fail logic is exact.
"""
import json

import pytest

from benchmarks import compare as bench_compare
from benchmarks import dae_table1
from conftest import dae_test_seed

PARITY_BENCHES = ("hist", "thr")  # the two cheapest kernels


# ---------------------------------------------------------------------------
# fork-pool determinism
# ---------------------------------------------------------------------------


def test_pool_rows_identical_to_sequential(capsys):
    """DAE_BENCH_JOBS>1 must produce byte-identical JSON rows to jobs=1."""
    seq = dae_table1.main(jobs=1, benches=PARITY_BENCHES)
    par = dae_table1.main(jobs=2, benches=PARITY_BENCHES)
    capsys.readouterr()  # silence the tables
    assert json.dumps(seq, sort_keys=True) == json.dumps(par, sort_keys=True)


def test_env_jobs_matches_explicit(monkeypatch, capsys):
    monkeypatch.setenv("DAE_BENCH_JOBS", "2")
    via_env = dae_table1.main(jobs=None, benches=PARITY_BENCHES)
    monkeypatch.delenv("DAE_BENCH_JOBS")
    explicit = dae_table1.main(jobs=1, benches=PARITY_BENCHES)
    capsys.readouterr()
    assert json.dumps(via_env, sort_keys=True) == \
        json.dumps(explicit, sort_keys=True)


@pytest.mark.parametrize("bad", ["banana", "1.5", "2 workers"])
def test_malformed_jobs_env_rejected(monkeypatch, bad):
    monkeypatch.setenv("DAE_BENCH_JOBS", bad)
    with pytest.raises(SystemExit, match="DAE_BENCH_JOBS"):
        dae_table1._resolve_jobs(None, 4)


def test_jobs_env_defaults_and_clamps(monkeypatch):
    monkeypatch.setenv("DAE_BENCH_JOBS", "0")
    assert dae_table1._resolve_jobs(None, 2) >= 1  # 0 = one per core
    monkeypatch.setenv("DAE_BENCH_JOBS", "64")
    assert dae_table1._resolve_jobs(None, 3) == 3  # clamped to task count
    monkeypatch.delenv("DAE_BENCH_JOBS")
    assert dae_table1._resolve_jobs(1, 9) == 1


# ---------------------------------------------------------------------------
# the --quick matrix
# ---------------------------------------------------------------------------


def test_quick_benches_subset():
    from repro.bench_irregular import ALL
    assert set(dae_table1.QUICK_BENCHES) < set(ALL)


def test_quick_flag_wires_reduced_matrix(monkeypatch, tmp_path, capsys):
    """run.py --quick must pass the reduced matrix to every DAE section
    and skip the jax sections entirely."""
    from benchmarks import dae_fig7, dae_quiescent, dae_table2, run as bench_run

    calls = {}

    def fake_table1(jobs=None, benches=None, **kw):
        calls["table1"] = {"jobs": jobs, "benches": benches}
        return [{"bench": "hist", "sta": 100, "dae": 300, "spec": 50,
                 "oracle": 45, "window_hit": 0.1, "pipe_hit": 0.1}]

    def fake_steady(benches=None, repeats=None, **kw):
        calls["steady"] = {"benches": benches, "repeats": repeats}
        return [{"bench": "spmv", "cycles": 1000, "cover": 0.9,
                 "grants": 5, "evt_ms": 2.0, "pipe_ms": 1.0,
                 "speedup": 2.0}]

    def fake_table2(rates=None, **kw):
        calls["table2"] = {"rates": rates}
        return {"hist": [100, 101, 102]}

    def fake_fig7(jobs=None, max_levels=None, **kw):
        calls["fig7"] = {"max_levels": max_levels}
        return [(1, 1, 1, 1, 0.0, 0.0)]

    def fake_quiescent(points=None, **kw):
        calls["quiescent"] = {"points": points}
        return {"speedup": 3.5, "hit": 0.9, "rows": []}

    def fake_codegen(benches=None, jax_benches=None, **kw):
        calls["codegen"] = {"benches": benches, "jax_benches": jax_benches}
        return {"spmv": {"interp_us": 10.0, "numpy_us": 10.0,
                         "numpy_x": 1.0, "jax_us": 100.0, "jax_x": 0.1}}

    def fake_chaos(repeats=None, budget_s=None, **kw):
        calls["chaos"] = {"repeats": repeats, "budget_s": budget_s}
        return "quiet_ovh_max=0.10%"

    def fake_serve(quick=False, **kw):
        calls["serve"] = {"quick": quick}
        return "bitexact=True,p50_ms=1.0,poison=0"

    def fake_frontend(repeats=None, **kw):
        calls["frontend"] = {"repeats": repeats}
        return {"pagerank": {"cold_ms": 3.0, "warm_ms": 0.5,
                             "warm_ratio": 6.0},
                "_cache": {"hits": 4, "misses": 4, "stale": 0,
                           "invalidated": 3, "hit_rate": 0.5}}

    from benchmarks import dae_chaos, dae_codegen, dae_frontend, moe_ab
    monkeypatch.setattr(dae_table1, "main", fake_table1)
    monkeypatch.setattr(dae_table1, "steady_ab", fake_steady)
    monkeypatch.setattr(dae_table2, "main", fake_table2)
    monkeypatch.setattr(dae_fig7, "main", fake_fig7)
    monkeypatch.setattr(dae_quiescent, "main", fake_quiescent)
    monkeypatch.setattr(dae_codegen, "main", fake_codegen)
    monkeypatch.setattr(dae_chaos, "main", fake_chaos)
    monkeypatch.setattr(moe_ab, "dae_serve", fake_serve)
    monkeypatch.setattr(dae_frontend, "main", fake_frontend)

    out = tmp_path / "bench.json"
    bench_run.main(["--quick", "--json", str(out)])
    capsys.readouterr()

    assert calls["table1"]["benches"] == dae_table1.QUICK_BENCHES
    assert calls["table1"]["jobs"] == 1  # quick defaults to sequential
    assert calls["steady"]["benches"] == dae_table1.STEADY_BENCHES[:2]
    assert calls["table2"]["rates"] == [0.0, 0.6, 1.0]
    assert calls["fig7"]["max_levels"] == 4
    assert calls["quiescent"]["points"] == dae_quiescent.QUICK_POINTS
    assert calls["codegen"]["jax_benches"] == ("spmv",)  # one jax leg
    assert calls["chaos"]["repeats"] == 8  # quick trades margin for wall
    assert calls["serve"]["quick"] is True  # serve A/B rides the quick gate
    assert calls["frontend"]["repeats"] == 3  # quick trims the A/B samples
    rows = json.loads(out.read_text())
    names = [r["name"] for r in rows]
    assert names == ["dae_table1", "dae_steady", "dae_table2", "dae_fig7",
                     "dae_quiescent", "dae_codegen", "dae_chaos",
                     "dae_serve", "dae_frontend"]
    assert "moe_ab" not in names and "kernel_bench" not in names
    fe = next(r for r in rows if r["name"] == "dae_frontend")
    assert "warm_ratio=6.00x" in fe["derived"]
    assert "hit_rate=0.50" in fe["derived"]


def test_window_flag_propagates(monkeypatch, tmp_path, capsys):
    from benchmarks import dae_fig7, dae_quiescent, dae_table2, run as bench_run
    import os

    seen = {}

    def fake_table1(jobs=None, benches=None, **kw):
        seen["window_env"] = os.environ.get("DAE_SIM_WINDOW")
        seen["pipeline_env"] = os.environ.get("DAE_SIM_PIPELINE")
        return [{"bench": "hist", "sta": 100, "dae": 300, "spec": 50,
                 "oracle": 45, "window_hit": 0.0, "pipe_hit": 0.0}]

    monkeypatch.setattr(dae_table1, "main", fake_table1)
    monkeypatch.setattr(dae_table1, "steady_ab",
                        lambda benches=None, repeats=None, **kw:
                        [{"bench": "spmv", "cycles": 1, "cover": 0.0,
                          "grants": 0, "evt_ms": 1.0, "pipe_ms": 1.0,
                          "speedup": 1.0}])
    monkeypatch.setattr(dae_table2, "main",
                        lambda rates=None, **kw: {"hist": [1, 1, 1]})
    monkeypatch.setattr(dae_fig7, "main",
                        lambda jobs=None, max_levels=None, **kw:
                        [(1, 1, 1, 1, 0.0, 0.0)])
    monkeypatch.setattr(dae_quiescent, "main",
                        lambda points=None, **kw:
                        {"speedup": 1.0, "hit": 0.0, "rows": []})
    from benchmarks import dae_chaos, dae_codegen, dae_frontend, moe_ab
    monkeypatch.setattr(dae_codegen, "main",
                        lambda benches=None, jax_benches=None, **kw:
                        {"spmv": {"interp_us": 1.0, "numpy_us": 1.0,
                                  "numpy_x": 1.0}})
    monkeypatch.setattr(dae_chaos, "main",
                        lambda repeats=None, budget_s=None, **kw:
                        "quiet_ovh_max=0.10%")
    monkeypatch.setattr(moe_ab, "dae_serve",
                        lambda quick=False, **kw: "bitexact=True,poison=0")
    monkeypatch.setattr(dae_frontend, "main",
                        lambda repeats=None, **kw:
                        {"join": {"cold_ms": 2.0, "warm_ms": 1.0,
                                  "warm_ratio": 2.0},
                         "_cache": {"hit_rate": 0.5}})
    bench_run.main(["--quick", "--json", str(tmp_path / "a.json")])
    assert seen["window_env"] == "1"
    assert seen["pipeline_env"] == "1"
    bench_run.main(["--quick", "--no-window",
                    "--json", str(tmp_path / "b.json")])
    assert seen["window_env"] == "0"
    assert seen["pipeline_env"] == "1"
    bench_run.main(["--quick", "--no-pipeline",
                    "--json", str(tmp_path / "c.json")])
    capsys.readouterr()
    assert seen["window_env"] == "1"
    assert seen["pipeline_env"] == "0"


# ---------------------------------------------------------------------------
# compare.py — the bench gate
# ---------------------------------------------------------------------------


def _write(path, rows):
    path.write_text(json.dumps(
        [{"name": n, "us_per_call": us, "derived": ""} for n, us in rows]))
    return str(path)


def test_gate_passes_within_tolerance(tmp_path, capsys):
    base = _write(tmp_path / "base.json", [("a", 100.0), ("b", 200.0)])
    new = _write(tmp_path / "new.json", [("a", 110.0), ("b", 150.0)])
    assert bench_compare.main([new, "--baseline", base]) == 0
    assert "PASS" in capsys.readouterr().out


def test_gate_fails_on_regression(tmp_path, capsys):
    base = _write(tmp_path / "base.json", [("a", 100.0), ("b", 200.0)])
    new = _write(tmp_path / "new.json", [("a", 126.0), ("b", 200.0)])
    assert bench_compare.main([new, "--baseline", base]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "a" in out


def test_gate_tolerance_flag(tmp_path, capsys):
    base = _write(tmp_path / "base.json", [("a", 100.0)])
    new = _write(tmp_path / "new.json", [("a", 150.0)])
    assert bench_compare.main([new, "--baseline", base,
                               "--tolerance", "0.6"]) == 0
    capsys.readouterr()


def test_gate_ignores_mismatched_sections(tmp_path, capsys):
    """quick vs full matrices differ; only the intersection is gated."""
    base = _write(tmp_path / "base.json", [("a", 100.0), ("full_only", 9.0)])
    new = _write(tmp_path / "new.json", [("a", 100.0), ("quick_only", 5.0)])
    assert bench_compare.main([new, "--baseline", base]) == 0
    capsys.readouterr()


def test_gate_rejects_empty_intersection(tmp_path):
    base = _write(tmp_path / "base.json", [("a", 100.0)])
    new = _write(tmp_path / "new.json", [("b", 100.0)])
    with pytest.raises(SystemExit, match="no common"):
        bench_compare.main([new, "--baseline", base])


def test_gate_rejects_malformed_rows(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"name": "a"}]))  # no us_per_call
    good = _write(tmp_path / "good.json", [("a", 1.0)])
    with pytest.raises(SystemExit, match="malformed"):
        bench_compare.main([str(bad), "--baseline", good])


@pytest.mark.parametrize("poison", ["nan", "inf", "-inf"])
def test_gate_rejects_non_finite_timings(tmp_path, poison):
    """float('nan') compares False against every threshold, so a crashed
    section would silently PASS the gate without the isfinite check."""
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        [{"name": "a", "us_per_call": poison, "derived": ""}]))
    good = _write(tmp_path / "good.json", [("a", 1.0)])
    with pytest.raises(SystemExit, match="non-finite"):
        bench_compare.main([str(bad), "--baseline", good])


def test_gate_require_missing_section_fails(tmp_path, capsys):
    """--require turns a silently dropped section into a loud failure
    (without it, a section missing from one file is just skipped)."""
    base = _write(tmp_path / "base.json", [("a", 100.0), ("b", 1.0)])
    new = _write(tmp_path / "new.json", [("a", 100.0)])
    # without --require the missing section is skipped and the gate passes
    assert bench_compare.main([new, "--baseline", base]) == 0
    capsys.readouterr()
    with pytest.raises(SystemExit, match=r"required section.*b"):
        bench_compare.main([new, "--baseline", base, "--require", "a,b"])


def test_gate_require_present_sections_pass(tmp_path, capsys):
    base = _write(tmp_path / "base.json", [("a", 100.0), ("b", 1.0)])
    new = _write(tmp_path / "new.json", [("a", 100.0), ("b", 1.0)])
    assert bench_compare.main([new, "--baseline", base,
                               "--require", "a,b"]) == 0
    capsys.readouterr()


def _write_derived(path, rows):
    path.write_text(json.dumps(
        [{"name": n, "us_per_call": us, "derived": d}
         for n, us, d in rows]))
    return str(path)


def test_gate_require_derived_key(tmp_path, capsys):
    """'section.key' --require entries reach into the derived string:
    missing-from-new fails, numeric regressions beyond tolerance fail,
    stable counters and keys new to this run pass."""
    base = _write_derived(tmp_path / "base.json",
                          [("cg", 100.0, "hist_calls=2,min=0.04x")])
    new = _write_derived(tmp_path / "new.json",
                         [("cg", 100.0, "hist_calls=2,min=0.05x,extra=1")])
    assert bench_compare.main([new, "--baseline", base,
                               "--require", "cg,cg.hist_calls"]) == 0
    # a key the baseline predates only warns
    assert bench_compare.main([new, "--baseline", base,
                               "--require", "cg.extra"]) == 0
    capsys.readouterr()
    # missing from the new file: loud failure
    with pytest.raises(SystemExit, match=r"cg\.nope.*missing"):
        bench_compare.main([new, "--baseline", base,
                            "--require", "cg.nope"])
    # a count regression fails even though wall time is identical
    worse = _write_derived(tmp_path / "worse.json",
                           [("cg", 100.0, "hist_calls=38")])
    with pytest.raises(SystemExit, match=r"cg\.hist_calls.*regressed"):
        bench_compare.main([worse, "--baseline", base,
                            "--require", "cg.hist_calls"])


def test_gate_require_floor_key(tmp_path, capsys):
    """'section.key>floor' gates a bigger-is-better metric: the new value
    must stay strictly above the floor, and the baseline is never
    consulted (so an improvement can't trip the regression check)."""
    base = _write_derived(tmp_path / "base.json",
                          [("fe", 100.0, "warm_ratio=1.80x")])
    better = _write_derived(tmp_path / "better.json",
                            [("fe", 100.0, "warm_ratio=9.50x")])
    # 9.5x vs 1.8x baseline: a plain derived-key require would call this
    # a regression; the floor gate passes it
    assert bench_compare.main([better, "--baseline", base,
                               "--require", "fe.warm_ratio>1"]) == 0
    assert "warm_ratio: 9.50x > 1 ok" in capsys.readouterr().out
    fell = _write_derived(tmp_path / "fell.json",
                          [("fe", 100.0, "warm_ratio=0.90x")])
    with pytest.raises(SystemExit, match=r"warm_ratio.*must stay > 1"):
        bench_compare.main([fell, "--baseline", base,
                            "--require", "fe.warm_ratio>1"])
    # the floored key must still exist and be numeric
    with pytest.raises(SystemExit, match=r"fe\.nope.*missing"):
        bench_compare.main([better, "--baseline", base,
                            "--require", "fe.nope>1"])
    texty = _write_derived(tmp_path / "texty.json",
                           [("fe", 100.0, "warm_ratio=fast")])
    with pytest.raises(SystemExit, match="must be numeric"):
        bench_compare.main([texty, "--baseline", base,
                            "--require", "fe.warm_ratio>1"])
    with pytest.raises(SystemExit, match="not numeric"):
        bench_compare.main([better, "--baseline", base,
                            "--require", "fe.warm_ratio>one"])


# ---------------------------------------------------------------------------
# DAE_TEST_SEED — the single fallback-seed knob
# ---------------------------------------------------------------------------


def test_test_seed_default_and_override(monkeypatch):
    monkeypatch.delenv("DAE_TEST_SEED", raising=False)
    assert dae_test_seed() == 0xDAE
    monkeypatch.setenv("DAE_TEST_SEED", "1234")
    assert dae_test_seed() == 1234
    monkeypatch.setenv("DAE_TEST_SEED", "0x10")
    assert dae_test_seed() == 16


def test_test_seed_malformed_rejected(monkeypatch):
    monkeypatch.setenv("DAE_TEST_SEED", "not-a-seed")
    with pytest.raises(RuntimeError, match="DAE_TEST_SEED"):
        dae_test_seed()


# ---------------------------------------------------------------------------
# repo hygiene: no stale bytecode ships
# ---------------------------------------------------------------------------


def test_no_bytecode_tracked_and_pycache_ignored():
    """Stale ``__pycache__`` bytecode must never be committed (it shadows
    edited sources in subtle ways) — nothing tracked may live under a
    ``__pycache__`` dir or end in ``.pyc``, and the ignore rules must
    cover ``benchmarks/__pycache__`` so it cannot come back."""
    import pathlib
    import shutil
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    if shutil.which("git") is None or not (root / ".git").exists():
        pytest.skip("not a git checkout")
    tracked = subprocess.run(["git", "ls-files"], cwd=root,
                             capture_output=True, text=True).stdout
    bad = [ln for ln in tracked.splitlines()
           if "__pycache__" in ln or ln.endswith(".pyc")]
    assert not bad, f"bytecode tracked in git: {bad}"
    ignored = subprocess.run(
        ["git", "check-ignore", "-q", "benchmarks/__pycache__/stale.pyc"],
        cwd=root).returncode == 0
    assert ignored, "benchmarks/__pycache__ is not git-ignored"


@pytest.mark.parametrize("path", [".jax_cache/entry", "chiprun_out/log"])
def test_runtime_dirs_git_ignored(path):
    """The compile cache and chip-run output are made at run time and
    must never be committed."""
    import pathlib
    import shutil
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    if shutil.which("git") is None or not (root / ".git").exists():
        pytest.skip("not a git checkout")
    assert subprocess.run(["git", "check-ignore", "-q", path],
                          cwd=root).returncode == 0


# ---------------------------------------------------------------------------
# entry-point process and cache hygiene
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache lives at the fixed ``<root>/.jax_cache``."""
    import jax

    from repro.launch.compile_cache import use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = use_compile_cache(str(tmp_path))
        if env_dir is None:
            want = str(tmp_path / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == want
        else:
            want = str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == prev
        assert got == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_pmap_sequential_once_jax_backend_live(monkeypatch):
    """A fork pool must not start from a process that holds a JAX
    runtime (on a TPU host, the chip): the map runs in-process."""
    import os

    monkeypatch.setattr(dae_table1, "_jax_backend_live", lambda: True)
    pids = dae_table1._pmap(_pid, [0, 1, 2], jobs=2)
    assert pids == [os.getpid()] * 3


def _pid(_):
    import os
    return os.getpid()
