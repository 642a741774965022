"""Master benchmark harness — one section per paper table/figure.

Emits a ``name,us_per_call,derived`` CSV summary at the end (harness
convention); `derived` carries the headline metric of each section.

``--json OUT`` additionally writes the rows to a JSON file (e.g.
``BENCH_machine.json``) so the perf trajectory is machine-readable across
PRs.  ``--quick`` runs a reduced matrix (small kernels, shallow nesting, coarse
rate sweep, no jax *model* sections, a single-kernel codegen jax leg) that
finishes in well under a minute — wired into ``make bench-quick``.  ``benchmarks/compare.py`` diffs two such
JSON drops and is the CI bench-gate.

The DAE sections run with batch-window execution and steady-state
pipeline windows enabled (the simulator's fast paths — see
``repro.core.machine``); pass ``--no-window`` / ``--no-pipeline`` for the
slower engines.  The ``dae_quiescent`` section always measures
batch-window on/off against each other, and the ``dae_steady`` section
A/Bs pipeline windows on the paper's load-dense kernels.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", dest="json_out", metavar="OUT", default=None,
                    help="write name/us_per_call/derived rows to a JSON file")
    ap.add_argument("--quick", action="store_true",
                    help="reduced matrix (<60 s): small kernels, shallow "
                         "nesting, no jax sections")
    ap.add_argument("--jobs", type=int, default=None,
                    help="worker processes for the DAE sections "
                         "(default: DAE_BENCH_JOBS or one per core; "
                         "1 = sequential)")
    ap.add_argument("--no-window", dest="window", action="store_false",
                    help="run the DAE sections on the plain event-stepped "
                         "engine instead of batch-window execution")
    ap.add_argument("--no-pipeline", dest="pipeline", action="store_false",
                    help="disable steady-state pipeline windows (the "
                         "multi-unit window engine) in the DAE sections")
    args = ap.parse_args(argv)
    # propagate the window opt-ins to fork-pool workers via the env knobs,
    # restoring the caller's values on exit (in-process callers like the
    # harness tests must not see their environment silently rewritten)
    prev = {k: os.environ.get(k)
            for k in ("DAE_SIM_WINDOW", "DAE_SIM_PIPELINE")}
    os.environ["DAE_SIM_WINDOW"] = "1" if args.window else "0"
    os.environ["DAE_SIM_PIPELINE"] = "1" if args.pipeline else "0"
    try:
        _run_sections(args)
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_sections(args) -> None:
    quick = args.quick
    if args.json_out:  # fail fast on an unwritable path, not after the
        # run — append mode probes without clobbering the previous artifact
        open(args.json_out, "a").close()
    # the quick matrix is too small to amortize pool spawn — default to
    # sequential there unless the caller asked for workers explicitly
    jobs = args.jobs if args.jobs is not None else (1 if quick else None)
    rows = []

    from benchmarks import dae_table1, dae_table2, dae_fig7

    print("=" * 72)
    print("Table 1 / Figure 6 — STA vs DAE vs SPEC vs ORACLE")
    print("=" * 72)
    t1, us1 = _timed(lambda: dae_table1.main(
        jobs=jobs,
        benches=dae_table1.QUICK_BENCHES if quick else None))

    def hm(xs):
        return len(xs) / sum(1.0 / x for x in xs)

    spec_hm = hm([r["sta"] / r["spec"] for r in t1])
    win_hit = sum(r["window_hit"] for r in t1) / len(t1)
    pipe_hit = sum(r["pipe_hit"] for r in t1) / len(t1)
    rows.append(("dae_table1", us1,
                 f"spec_hm_speedup={spec_hm:.2f}x,win_hit={win_hit:.3f},"
                 f"pipe_hit={pipe_hit:.3f}"))

    print()
    print("=" * 72)
    print("Steady-state pipeline windows — load-dense sim A/B "
          "(event vs pipeline engine)")
    print("=" * 72)
    sb = (dae_table1.STEADY_BENCHES[:2] if quick
          else dae_table1.STEADY_BENCHES)
    st, uss = _timed(lambda: dae_table1.steady_ab(
        benches=sb, repeats=3 if quick else 7))
    hdr = (f"{'bench':6s} {'cycles':>8s} {'cover':>6s} {'grants':>7s} "
           f"{'evt ms':>8s} {'pipe ms':>8s} {'speedup':>8s}")
    print(hdr)
    print("-" * len(hdr))
    for r in st:
        print(f"{r['bench']:6s} {r['cycles']:8d} {100 * r['cover']:5.1f}% "
              f"{r['grants']:7d} {r['evt_ms']:8.2f} {r['pipe_ms']:8.2f} "
              f"{r['speedup']:7.2f}x")
    derived = ",".join(f"{r['bench']}={r['speedup']:.2f}x/{r['cover']:.2f}"
                       for r in st)
    rows.append(("dae_steady", uss,
                 f"{derived},min_cover={min(r['cover'] for r in st):.2f}"))

    print()
    print("=" * 72)
    print("Table 2 — mis-speculation-rate sweep (SPEC cycles)")
    print("=" * 72)
    t2, us2 = _timed(lambda: dae_table2.main(
        rates=[0.0, 0.6, 1.0] if quick else None))
    import statistics
    worst = max(statistics.pstdev(v) / statistics.mean(v)
                for v in t2.values())
    rows.append(("dae_table2", us2, f"worst_rel_sigma={worst:.3f}"))

    print()
    print("=" * 72)
    print("Figure 7 — nested control flow scaling")
    print("=" * 72)
    f7, us7 = _timed(lambda: dae_fig7.main(
        jobs=jobs, max_levels=4 if quick else 8))
    ok = all(pc == expc for (_, _, pc, expc, _, _) in f7)
    rows.append(("dae_fig7", us7, f"poison_call_formula_holds={ok}"))

    print()
    print("=" * 72)
    print("Quiescent-heavy sim A/B — batch-window vs event-stepped engine")
    print("=" * 72)
    from benchmarks import dae_quiescent
    qr, usq = _timed(lambda: dae_quiescent.main(
        points=dae_quiescent.QUICK_POINTS if quick else None))
    rows.append(("dae_quiescent", usq,
                 f"win_speedup={qr['speedup']:.2f}x,win_hit={qr['hit']:.3f}"))

    print()
    print("=" * 72)
    print("Executable codegen — generated numpy/jax kernels vs interp.run")
    print("=" * 72)
    from benchmarks import dae_codegen
    # quick keeps one jax leg (spmv) so the gate still covers the Pallas
    # path without paying two interpret-mode compiles; the vectorised
    # state-machine-vs-cu-vector A/B trio always runs (it is the
    # ROADMAP-named acceptance number for the vector path)
    cg, uscg = _timed(lambda: dae_codegen.main(
        jax_benches=("spmv",) if quick else None))
    nx = min(r["numpy_x"] for r in cg.values() if "numpy_x" in r)
    nvx = [r["npvec_x"] for r in cg.values() if "npvec_x" in r]
    parts = [f"numpy_min={nx:.2f}x"]
    if nvx:
        parts.append(f"npvec_min={min(nvx):.2f}x")
    parts += [f"{k}_jax={r['jax_x']:.3f}x" for k, r in cg.items()
              if "jax_x" in r]
    parts += [f"{k}_jaxv={r['jaxv_x']:.1f}x" for k, r in cg.items()
              if "jaxv_x" in r]
    # absolute epoch/kernel-call counts from the forwarding A/B: the
    # bench gate checks these don't grow (a forwarding regression shows
    # up as a count jump long before it shows up in wall time)
    for k, r in cg.items():
        if "epochs" in r:
            parts.append(f"{k}_epochs={r['epochs']}")
            parts.append(f"{k}_calls={r['calls']}")
    rows.append(("dae_codegen", uscg, ",".join(parts)))

    print()
    print("=" * 72)
    print("Resilience — armed-but-quiet fault-plane overhead on the "
          "codegen legs")
    print("=" * 72)
    from benchmarks import dae_chaos
    # quick trades statistical margin for wall time; the hard <2% gate
    # runs in the dedicated `make chaos` leg at the full budget
    ch, usch = _timed(lambda: dae_chaos.main(
        repeats=8 if quick else 40, budget_s=0.5 if quick else 4.0))
    rows.append(("dae_chaos", usch, ch))

    print()
    print("=" * 72)
    print("Serving A/B — spec-kernel vs lax-scatter vs dense under "
          "continuous traffic")
    print("=" * 72)
    # runs in quick AND full: the bit-exactness assertion and the exact
    # poison counter are the CI gate for the whole speculative
    # data-movement layer (compare.py --require dae_serve.poison)
    from benchmarks import moe_ab as moe_ab_mod
    sv, ussv = _timed(lambda: moe_ab_mod.dae_serve(quick=quick))
    rows.append(("dae_serve", ussv, sv))

    print()
    print("=" * 72)
    print("Frontend compile cache — cold vs warm compile A/B "
          "(pagerank + join)")
    print("=" * 72)
    # runs in quick AND full: the bench asserts warm < cold and bit-exact
    # warm kernels, and the derived warm_ratio is the CI floor gate
    # (compare.py --require dae_frontend.warm_ratio>1)
    from benchmarks import dae_frontend
    fr, usfr = _timed(lambda: dae_frontend.main(
        repeats=3 if quick else 7))
    fams = [k for k in fr if not k.startswith("_")]
    parts = [f"warm_ratio={min(fr[k]['warm_ratio'] for k in fams):.2f}x",
             f"hit_rate={fr['_cache']['hit_rate']:.2f}"]
    parts += [f"{k}_warm_ratio={fr[k]['warm_ratio']:.2f}x" for k in fams]
    parts += [f"{k}_cold_ms={fr[k]['cold_ms']:.2f}" for k in fams]
    rows.append(("dae_frontend", usfr, ",".join(parts)))

    if not quick:
        # the paper's technique inside the LM framework: MoE dispatch A/B
        print()
        print("=" * 72)
        print("MoE dispatch A/B — speculative (capacity+poison) vs dense")
        print("=" * 72)
        from benchmarks import moe_ab
        ab, usab = _timed(moe_ab.main)
        rows.append(("moe_ab", usab, ab))

        print()
        print("=" * 72)
        print("Kernel micro-benches (Pallas interpret vs jnp reference)")
        print("=" * 72)
        try:
            from benchmarks import kernel_bench
            kb, usk = _timed(kernel_bench.main)
            rows.append(("kernel_bench", usk, kb))
        except ImportError:
            pass

        # roofline summary from the latest dry-run artifacts, if present
        try:
            from benchmarks import roofline_report
            rr, usr = _timed(roofline_report.main)
            rows.append(("roofline_report", usr, rr))
        except ImportError:
            pass

    print()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")

    if args.json_out:
        payload = [{"name": name, "us_per_call": round(us, 1),
                    "derived": str(derived)} for name, us, derived in rows]
        with open(args.json_out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"\nwrote {len(payload)} rows to {args.json_out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
