"""Table 1 / Figure 6 reproduction: STA, DAE, SPEC, ORACLE cycle counts,
mis-speculation rates, poison block/call counts, and a code-size proxy for
the paper's ALM area (CU+AGU instruction & block counts).

The nine kernels are independent simulations, so they fan out across a
process pool by default (``jobs=0`` → one worker per core); pass ``jobs=1``
(or set ``DAE_BENCH_JOBS=1``) for the sequential path.  Results are
byte-identical either way — each worker runs the same deterministic
pipeline and rows are collected in kernel order.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Dict, Optional

import numpy as np

from repro.bench_irregular import ALL
from repro.core import pipeline
from repro.core.machine import MachineConfig


def _resolve_jobs(jobs: Optional[int], n_tasks: int) -> int:
    if jobs is None:
        raw = os.environ.get("DAE_BENCH_JOBS", "0").strip() or "0"
        try:
            jobs = int(raw)
        except ValueError:
            raise SystemExit(
                f"DAE_BENCH_JOBS must be an integer "
                f"(0 = one worker per core), got {raw!r}") from None
    if jobs <= 0:
        jobs = os.cpu_count() or 1
    return max(1, min(jobs, n_tasks))


def _jax_backend_live() -> bool:
    """True once this process has initialised a JAX backend."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge  # JAX has no public query for this
    return xla_bridge.backends_are_initialized()


def _pmap(fn, args, jobs, weights=None):
    """Order-preserving map over a fork pool (sequential when jobs==1).

    ``weights`` (heavier = dispatched first) avoids a long task landing
    last on an otherwise-drained pool; results come back in input order.
    Once this process has initialised a JAX backend the map runs
    sequentially: a forked child would inherit the live runtime, and on a
    TPU host the chip, which belongs to one process at a time.
    """
    if jobs == 1 or _jax_backend_live():
        return [fn(a) for a in args]
    import multiprocessing as mp
    try:
        ctx = mp.get_context("fork")  # inherit loaded modules, cheap spawn
    except ValueError:  # pragma: no cover - non-fork platforms
        return [fn(a) for a in args]
    order = list(range(len(args)))
    if weights is not None:
        order.sort(key=lambda i: -weights[i])
    with ctx.Pool(processes=jobs) as pool:
        res = pool.map(fn, [args[i] for i in order], chunksize=1)
    out = [None] * len(args)
    for pos, i in enumerate(order):
        out[i] = res[pos]
    return out


# rough relative simulation cost per kernel — a dispatch hint only
_WEIGHTS = {"fw": 100, "sort": 50, "sssp": 40, "bc": 30, "bfs": 25,
            "hist": 10, "mm": 8, "spmv": 6, "thr": 4}


def code_size(fn) -> int:
    return sum(len(b.phis) + len(b.body) + 1 for b in fn.blocks.values())


def run_one(name: str, cfg: MachineConfig = None) -> Dict:
    case = ALL[name]()
    runs = pipeline.run_all(case.fn, case.decoupled, case.memory,
                            params=case.params, cfg=cfg)
    ref = runs["ref"].memory
    for v in ("sta", "dae", "spec"):
        for k in ref:
            assert np.array_equal(runs[v].memory[k], ref[k]), \
                f"{name}/{v}: memory diverges from sequential reference"
    spec = runs["spec"]
    comp = spec.compiled
    row = {
        "bench": name,
        "note": case.note,
        "sta": runs["sta"].cycles,
        "dae": runs["dae"].cycles,
        "spec": spec.cycles,
        "oracle": runs["oracle"].cycles,
        "speedup_spec_vs_sta": round(runs["sta"].cycles / spec.cycles, 2),
        "slowdown_dae_vs_sta": round(runs["sta"].cycles / runs["dae"].cycles, 2),
        "spec_vs_oracle": round(spec.cycles / runs["oracle"].cycles, 3),
        "misspec_rate": round(spec.result.misspec_rate, 3),
        "poison_blocks": comp.poison_stats.poison_blocks,
        "poison_calls": comp.poison_stats.poison_calls,
        "merged_blocks": comp.poison_stats.merged_blocks,
        "size_sta": code_size(case.fn),
        "size_spec": code_size(comp.agu) + code_size(comp.cu),
        "spec_requests": comp.spec.spec_requests,
        "fallbacks": len(comp.spec.fallback),
        # window diagnostics (0.0 unless DAE_SIM_WINDOW / DAE_SIM_PIPELINE
        # / cfg opts in): combined coverage + the pipeline-window share
        "window_hit": round(spec.result.window_hit_rate, 3),
        "pipe_hit": round(spec.result.pipeline_hit_rate, 3),
    }
    return row


QUICK_BENCHES = ("hist", "thr", "mm", "spmv")  # the small kernels

# the load-dense kernels the steady-state A/B reports on: memory-bound
# shapes where the AGU/CU/LSQ set is busy nearly every cycle, so the
# quiescent batch window of PR 2 almost never fired (~2-10% hit)
STEADY_BENCHES = ("spmv", "hist", "sort", "fw")


def steady_ab(benches=STEADY_BENCHES, repeats: int = 7):
    """Sim-only A/B on the load-dense kernels: event-stepped engine vs
    steady-state pipeline windows (``MachineConfig(pipeline_window=True)``)
    on the same compiled SPEC slices.  Runs are interleaved so box drift
    cancels; results are asserted bit-identical before timing is trusted.
    Returns one row per kernel with the wall speedup and the fraction of
    simulated cycles covered by pipeline windows."""
    import time

    from repro.core import machine

    rows = []
    for name in benches:
        case = ALL[name]()
        comp = pipeline.compile_spec(case.fn, case.decoupled)

        def once(pipe: bool):
            mem = {k: v.copy() for k, v in case.memory.items()}
            # pin batch windows off on both sides: this is the
            # event-stepped vs pipeline A/B and must not inherit the
            # DAE_SIM_WINDOW opt-in run.py exports for the other sections
            cfg = MachineConfig(batch_window=False, pipeline_window=pipe)
            r = machine.run_dae(comp.agu, comp.cu, mem, case.decoupled,
                                case.params, cfg)
            return r, mem

        r_evt, m_evt = once(False)
        r_pipe, m_pipe = once(True)
        assert r_evt.cycles == r_pipe.cycles, f"{name}: cycles diverged"
        for k in m_evt:
            assert np.array_equal(m_evt[k], m_pipe[k]), \
                f"{name}: memory diverged under pipeline windows"
        b_evt = b_pipe = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            once(False)
            b_evt = min(b_evt, time.perf_counter() - t0)
            t0 = time.perf_counter()
            once(True)
            b_pipe = min(b_pipe, time.perf_counter() - t0)
        rows.append({
            "bench": name,
            "cycles": r_pipe.cycles,
            "cover": round(r_pipe.pipeline_hit_rate, 3),
            "grants": r_pipe.pipeline_grants,
            "evt_ms": round(b_evt * 1e3, 2),
            "pipe_ms": round(b_pipe * 1e3, 2),
            "speedup": round(b_evt / b_pipe, 2),
        })
    return rows


def main(out_json: str = None, jobs: Optional[int] = None,
         benches=None):
    names = [n for n in ALL if benches is None or n in benches]
    rows = _pmap(run_one, names, _resolve_jobs(jobs, len(names)),
                 weights=[_WEIGHTS.get(n, 1) for n in names])
    hdr = (f"{'bench':6s} {'STA':>8s} {'DAE':>8s} {'SPEC':>8s} {'ORACLE':>8s} "
           f"{'SPECvSTA':>9s} {'SPEC/ORC':>9s} {'mis%':>6s} {'pB':>3s} {'pC':>3s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['bench']:6s} {r['sta']:8d} {r['dae']:8d} {r['spec']:8d} "
              f"{r['oracle']:8d} {r['speedup_spec_vs_sta']:8.2f}x "
              f"{r['spec_vs_oracle']:9.3f} {100*r['misspec_rate']:5.1f}% "
              f"{r['poison_blocks']:3d} {r['poison_calls']:3d}")
    def hm(xs):
        return len(xs) / sum(1.0 / x for x in xs)

    print(f"\nharmonic-mean speedups vs STA:  "
          f"DAE={hm([r['sta']/r['dae'] for r in rows]):.2f}x  "
          f"SPEC={hm([r['sta']/r['spec'] for r in rows]):.2f}x  "
          f"ORACLE={hm([r['sta']/r['oracle'] for r in rows]):.2f}x")
    print("paper (Table 1):                DAE=0.31x  SPEC=1.96x  ORACLE=2.08x")
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(rows, fh, indent=2)
    return rows


if __name__ == "__main__":
    main()
