#!/usr/bin/env python3
"""Chip smoke test: the speculative serving path and the codegen ``jax``
target, end to end on a TPU, through the entry points a user calls.

    python chip_smoke.py                # one chip: serve + codegen phases
    python chip_smoke.py --four-chips   # expert-parallel MoE layer, 4 chips

Phases (one process; the chip belongs to one process at a time):

* **serve** — :class:`repro.serve.engine.Engine` with
  ``dispatch="spec-kernel"`` (dispatch and combine through the Pallas
  ``spec_scatter_add`` / ``spec_gather`` kernels) on Kimi K2 at its full
  widths, cut in depth, expert count and vocabulary to fit one chip,
  random weights from ``--seed``.  A second engine with
  ``dispatch="spec"`` (the lax reference) on the same weights must commit
  exactly the same tokens.
* **codegen** — ``repro.codegen.run(..., target="jax", cu_mode="vector")``
  on ``hist`` and ``spmv``; final memory must equal ``interp.run``'s bit
  for bit, on the ``jax`` rung, with no ladder descent.
* **four-chips** (only with ``--four-chips``) — ``moe.moe_spec`` under a
  ``(1, 4)`` ``data x model`` mesh with the experts sharded over
  ``model`` (the expert-parallel variant), kernel against lax dispatch
  and against the single-chip flat layer.

The script fails unless JAX's backend is a TPU and the kernels compile
(no interpret mode).  Phase wall times are set-up times of a smoke run,
not measurements.  The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: interpreter / stream step budget for the codegen phase (the default
#: 2M guards tests; these inputs take tens of millions of steps)
MAX_STEPS = 10 ** 9

#: codegen workloads and their build sizes: hist at a million elements
#: over 64K bins; spmv at the largest size ``interp.run`` finishes in
#: about 30 s on one host core (n=4096, ~6.7M non-zeros)
CODEGEN = (("hist", dict(n=1 << 20, n_bins=1 << 16)),
           ("spmv", dict(n=4096)))

#: the four-chip phase's MoE layer: 32 experts (8 resident per chip) at
#: Kimi's widths, 2048 tokens; at capacity factor 1.0 the random router
#: overflows some experts, so poisoned dispatches are compared too (at
#: 1.25 none overflow)
EP_LAYER = dict(n_experts=32, d_model=7168, d_ff=2048, top_k=8, tokens=2048,
                capacity_factor=1.0)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(min_devices: int):
    """The device list, or a failure when JAX has no TPU or the Pallas
    kernels would run in interpret mode."""
    import jax

    from repro.kernels.backend import resolve_interpret
    backend = jax.default_backend()
    check(backend == "tpu", f"JAX backend is {backend!r}, not a TPU")
    check(not resolve_interpret(),
          "Pallas kernels would run in interpret mode "
          f"(DAE_PALLAS_INTERPRET={os.environ.get('DAE_PALLAS_INTERPRET')!r})")
    devs = jax.devices()
    check(len(devs) >= min_devices,
          f"needs {min_devices} devices, JAX reports {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_config():
    """Kimi K2 at full widths, cut to one chip (about 7.5 GB of bf16)."""
    from repro.configs.base import get
    full = get("kimi_k2_1t_a32b")
    cut = dict(n_layers=4, n_experts=16, vocab=full.vocab // 8)
    cfg = dataclasses.replace(full, **cut)
    print(f"serve config: {full.name} at full widths (d_model={cfg.d_model}"
          f" heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.hd}"
          f" expert_ff={cfg.moe_d_ff} top_k={cfg.top_k}"
          f" shared={cfg.n_shared_experts} {cfg.dtype}); cut: "
          + ", ".join(f"{k} {getattr(full, k)}->{v}" for k, v in cut.items()),
          flush=True)
    return cfg


def phase_serve(seed: int) -> dict:
    import jax
    import numpy as np

    from repro.models.model import build_model
    from repro.serve.engine import Engine, Request

    cfg = serve_config()
    t0 = time.perf_counter()
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    times = {"init_s": time.perf_counter() - t0}

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in rng.integers(128, 513, 4)]
    print(f"serve traffic: {len(prompts)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, 16 new tokens each", flush=True)

    def serve(dispatch: str):
        eng = Engine(cfg, params, slots=4, max_len=1024, dispatch=dispatch)
        reqs = [Request(rid=i, prompt=p, max_new=16)
                for i, p in enumerate(prompts)]
        t = time.perf_counter()
        out = eng.run(reqs)
        times[f"{dispatch}_s"] = time.perf_counter() - t
        # Engine.run contains faults and always returns: a failed,
        # truncated or retried request shows only here
        check(not eng.events, f"{dispatch}: engine events {eng.events}")
        check(not any(r.failed or r.truncated for r in reqs),
              f"{dispatch}: failed or truncated requests")
        check(sorted(out) == list(range(len(reqs))),
              f"{dispatch}: results for {sorted(out)}")
        for r in reqs:
            toks = out[r.rid]
            check(len(toks) == r.max_new
                  and all(0 <= t < cfg.vocab for t in toks),
                  f"{dispatch}: request {r.rid} committed {toks}")
        poison = sum(w.moe_poison for w in eng.wave_stats)
        return out, poison

    got, poison = serve("spec-kernel")
    want, poison_ref = serve("spec")
    check(got == want, f"spec-kernel tokens {got} != lax reference {want}")
    check(poison == poison_ref,
          f"poisoned dispatches {poison} != lax reference {poison_ref}")
    print(f"serve: spec-kernel committed the lax reference's tokens "
          f"({sum(map(len, got.values()))} tokens, {poison} poisoned "
          f"dispatch requests)", flush=True)
    return times


# ---------------------------------------------------------------------------
# codegen
# ---------------------------------------------------------------------------


def phase_codegen() -> dict:
    import numpy as np

    from repro import codegen
    from repro.bench_irregular import ALL
    from repro.core import interp, pipeline

    times = {}
    for name, kw in CODEGEN:
        case = ALL[name](**kw)
        comp = pipeline.compile_spec(case.fn, case.decoupled)
        ref = {k: v.copy() for k, v in case.memory.items()}
        t = time.perf_counter()
        interp.run(case.fn, ref, case.params, max_steps=MAX_STEPS)
        times[f"{name}_interp_s"] = time.perf_counter() - t
        mem = {k: v.copy() for k, v in case.memory.items()}
        t = time.perf_counter()
        r = codegen.run(comp, mem, case.params, target="jax",
                        cu_mode="vector", max_steps=MAX_STEPS)
        times[f"{name}_jax_s"] = time.perf_counter() - t
        check(r.target_used == "jax",
              f"{name}: ran on {r.target_used!r}: {r.fallback_reason}")
        check(r.cu_mode == "vector", f"{name}: cu_mode {r.cu_mode!r}")
        check(not r.events, f"{name}: ladder events {r.events}")
        diff = [k for k in ref if not np.array_equal(ref[k], mem[k])]
        check(not diff, f"{name}: memory differs from interp in {diff}")
        print(f"codegen {name} ({case.note}): bit-exact vs interp, "
              f"{r.stats.get('epochs')} epochs, "
              f"{r.stats.get('gather_calls')} gathers, "
              f"{r.stats.get('scatter_calls')} scatters", flush=True)
    return times


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips(seed: int) -> dict:
    """Expert-parallel speculative MoE layer (:data:`EP_LAYER`) on a
    (1, 4) mesh in float32 — kernel against lax dispatch, and against one
    chip's flat layer."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import moe

    n_experts, d, ff, top_k, n = (EP_LAYER[k] for k in (
        "n_experts", "d_model", "d_ff", "top_k", "tokens"))
    kw = dict(n_experts=n_experts, top_k=top_k,
              capacity_factor=EP_LAYER["capacity_factor"])
    t0 = time.perf_counter()
    draw = jax.jit(lambda k, s: jax.random.normal(k, s, jnp.float32) * 0.02,
                   static_argnums=1)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    params = {"router": draw(ks[0], (d, n_experts)),
              "w_gate": draw(ks[1], (n_experts, d, ff)),
              "w_up": draw(ks[2], (n_experts, d, ff)),
              "w_down": draw(ks[3], (n_experts, ff, d))}
    x = jax.random.normal(ks[4], (n, d), jnp.float32)

    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    experts = NamedSharding(mesh, P("model", None, None))
    replicated = NamedSharding(mesh, P())
    ep_params = {k: jax.device_put(v, experts if v.ndim == 3 else replicated)
                 for k, v in params.items()}
    ep_x = jax.device_put(x, replicated)
    for k in ("w_gate", "w_up", "w_down"):
        check(len(ep_params[k].sharding.device_set) == 4,
              f"{k} spans {ep_params[k].sharding.device_set}")

    def ep(kernel):
        f = jax.jit(functools.partial(moe.moe_spec, kernel=kernel,
                                      stats=True, **kw))
        with jax.set_mesh(mesh):
            compiled = f.lower(ep_params, ep_x).compile()
            out, poison = compiled(ep_params, ep_x)
        # the expert-parallel body combines with one psum over ``model``
        check("all-reduce" in compiled.as_text(),
              "moe_spec did not take the EP path")
        return np.asarray(out), int(poison)

    ker, pois_ker = ep(kernel=True)
    ref, pois_ref = ep(kernel=False)
    flat, pois_flat = jax.jit(functools.partial(
        moe._moe_spec_flat, stats=True, **kw))(params, x)
    flat, pois_flat = np.asarray(flat), int(pois_flat)
    times = {"four_chips_s": time.perf_counter() - t0}

    check(np.array_equal(ker, ref), "EP spec-kernel != EP lax, bitwise")
    check(pois_ker == pois_ref == pois_flat > 0,
          f"poison EP kernel {pois_ker}, EP lax {pois_ref}, flat "
          f"{pois_flat} (must be equal, and some dispatch poisoned)")
    err = float(np.max(np.abs(ker - flat)))
    scale = float(np.max(np.abs(flat)))
    check(np.allclose(ker, flat, rtol=1e-5, atol=1e-5 * scale),
          f"EP vs flat: max abs diff {err} at output scale {scale}")
    print(f"four-chips: EP kernel == EP lax bitwise; poison {pois_ker} "
          f"== flat; max |EP - flat| {err} (output scale {scale})",
          flush=True)
    return times


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and inputs")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the expert-parallel MoE phase on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache(ROOT)
    try:
        devs = require_tpu(4 if args.four_chips else 1)
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        print(f"device: {dev}; compile cache: {cache}", flush=True)
        times = {}
        if args.four_chips:
            times.update(phase_four_chips(args.seed))
        else:
            times.update(phase_serve(args.seed))
            times.update(phase_codegen())
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print("set-up wall times of this smoke run (not metrics): "
          + ", ".join(f"{k}={v!r}" for k, v in times.items()), flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
