"""Differential + serving tests for the speculative data-movement layer.

Three concerns, one file:

* **Dispatch differential suite** — the spec-kernel MoE path
  (``spec_scatter_add``/``spec_gather``) must be *bit-identical* to the
  lax-scatter reference on every mesh variant (flat / expert-parallel /
  tensor-parallel), with capacity-overflow poison counted identically;
  dense is the numerical cross-check on non-poisoned tokens.
* **Serving-semantics bugfixes** — left-pad poisoning (batched waves
  bit-match solo runs), explicit truncation events, per-wave stats, and
  the continuous-traffic harness.
* **Interpret-mode resolution regression** — the Pallas wrappers must
  read ``DAE_PALLAS_INTERPRET`` / ``resolve_interpret`` *per call*,
  outside the jitted core (the old ``interpret: bool = True`` jit-static
  default baked the first trace's value into the cache).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import base
from repro.models import moe
from repro.models.model import build_model
from repro.resilience import faults
from repro.resilience.faults import FaultPlan
from repro.serve.engine import Engine, Request
from repro.serve.traffic import TrafficConfig, make_requests, run_traffic

CFG = base.smoke(base.get("kimi_k2_1t_a32b"))        # moe family
DENSE_CFG = base.smoke(base.get("granite_34b"))      # dense family


def _moe_params(key: int = 0):
    m = build_model(CFG)
    groups = m.init(jax.random.PRNGKey(key))["groups"]
    return jax.tree.map(lambda a: a[0], groups)["s1_moe"]


def _x(n: int = 64, key: int = 1):
    return jax.random.normal(jax.random.PRNGKey(key), (n, CFG.d_model),
                             jnp.float32)


AUTO2 = (jax.sharding.AxisType.Auto,) * 2


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO2)


# ---------------------------------------------------------------------------
# dispatch differential suite
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cf", [1.25, 0.5])
def test_spec_kernel_bitexact_flat(cf):
    """Kernel dispatch == lax reference, bitwise, with and without
    capacity-overflow poison."""
    p, x = _moe_params(), _x()
    kw = dict(n_experts=CFG.n_experts, top_k=CFG.top_k, capacity_factor=cf)
    ref, pois_ref = moe._moe_spec_flat(p, x, stats=True, **kw)
    ker, pois_ker = moe._moe_spec_flat(p, x, kernel=True, stats=True, **kw)
    assert bool((ref == ker).all()), "spec-kernel diverged from lax path"
    assert int(pois_ref) == int(pois_ker)
    if cf == 0.5:
        assert int(pois_ref) > 0, "low capacity must overflow"
    else:
        assert int(pois_ref) < x.shape[0] * CFG.top_k


@pytest.mark.parametrize("cf", [1.25, 0.5])
def test_spec_kernel_bitexact_ep_mesh(cf):
    """Expert-parallel variant (1-device model axis) == flat, both paths,
    poison counted identically."""
    p, x = _moe_params(), _x()
    kw = dict(n_experts=CFG.n_experts, top_k=CFG.top_k, capacity_factor=cf)
    flat, pois_flat = moe._moe_spec_flat(p, x, stats=True, **kw)
    mesh = _mesh11()
    with jax.set_mesh(mesh):
        ref, pois_ref = moe._moe_spec_ep(p, x, mesh=mesh, stats=True, **kw)
        ker, pois_ker = moe._moe_spec_ep(p, x, mesh=mesh, kernel=True,
                                         stats=True, **kw)
    assert bool((ref == ker).all())
    assert int(pois_ref) == int(pois_ker) == int(pois_flat)
    np.testing.assert_allclose(np.asarray(flat), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("cf", [1.25, 0.5])
def test_spec_kernel_bitexact_tp_mesh(cf):
    """Tensor-parallel variant (1-device model axis) == flat, both paths,
    poison counted identically."""
    p, x = _moe_params(), _x()
    kw = dict(n_experts=CFG.n_experts, top_k=CFG.top_k, capacity_factor=cf)
    _, pois_flat = moe._moe_spec_flat(p, x, stats=True, **kw)
    mesh = _mesh11()
    with jax.set_mesh(mesh):
        ref, pois_ref = moe._moe_spec_tp(p, x, mesh=mesh, stats=True, **kw)
        ker, pois_ker = moe._moe_spec_tp(p, x, mesh=mesh, kernel=True,
                                         stats=True, **kw)
    assert bool((ref == ker).all())
    assert int(pois_ref) == int(pois_ker) == int(pois_flat)


def test_spec_kernel_bitexact_ep_multidevice():
    """Non-resident experts poisoned per shard, yet the committed result
    and the global poison count match the flat reference."""
    if jax.device_count() < 2:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count")
    p, x = _moe_params(), _x()
    kw = dict(n_experts=CFG.n_experts, top_k=CFG.top_k, capacity_factor=0.5)
    _, pois_flat = moe._moe_spec_flat(p, x, stats=True, **kw)
    mesh = jax.make_mesh((1, 2), ("data", "model"), axis_types=AUTO2)
    with jax.set_mesh(mesh):
        ref, pois_ref = moe._moe_spec_ep(p, x, mesh=mesh, stats=True, **kw)
        ker, pois_ker = moe._moe_spec_ep(p, x, mesh=mesh, kernel=True,
                                         stats=True, **kw)
    assert bool((ref == ker).all())
    # each request's home shard sees the same per-expert arrival order as
    # the flat run, so the capacity-race losers are the same set
    assert int(pois_ref) == int(pois_ker) == int(pois_flat)


def test_moe_spec_routes_to_ep_under_mesh():
    """The public entry point picks the expert-parallel variant under a
    model-axis mesh and still honors kernel/stats."""
    p, x = _moe_params(), _x()
    kw = dict(n_experts=CFG.n_experts, top_k=CFG.top_k, capacity_factor=1.25)
    with jax.set_mesh(_mesh11()):
        out, pois = moe.moe_spec(p, x, kernel=True, stats=True, **kw)
    ref = moe._moe_spec_flat(p, x, **kw)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_spec_matches_dense_when_unpoisoned():
    """With generous capacity (zero poison) the speculative paths agree
    numerically with the dense if-converted baseline."""
    p, x = _moe_params(), _x(n=32)
    kw = dict(n_experts=CFG.n_experts, top_k=CFG.top_k)
    spec, pois = moe._moe_spec_flat(p, x, capacity_factor=4.0, stats=True,
                                    **kw)
    kern = moe._moe_spec_flat(p, x, capacity_factor=4.0, kernel=True, **kw)
    dense, dpois = moe.moe_dense(p, x, stats=True, **kw)
    assert int(pois) == 0 and int(dpois) == 0
    assert bool((spec == kern).all())
    np.testing.assert_allclose(np.asarray(spec), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)


def test_model_dispatch_spec_kernel_bitexact():
    """End-to-end prefill/decode: dispatch="spec-kernel" is bit-identical
    to dispatch="spec" and reports poison stats."""
    m_ref = build_model(CFG, "spec")
    m_ker = build_model(CFG, "spec-kernel")
    params = m_ref.init(jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 1, CFG.vocab)
    pads = jnp.array([0, 3], jnp.int32)
    l_ref, c_ref = m_ref.prefill(params, tok, 16, pad_lens=pads)
    l_ker, c_ker, st = m_ker.prefill(params, tok, 16, pad_lens=pads,
                                     return_stats=True)
    assert bool((l_ref == l_ker).all())
    assert int(st["moe_poison"]) >= 0
    d_ref, _ = m_ref.decode_step(params, c_ref, tok[:, -1:], 8,
                                 pad_lens=pads)
    d_ker, _, st2 = m_ker.decode_step(params, c_ker, tok[:, -1:], 8,
                                      pad_lens=pads, return_stats=True)
    assert bool((d_ref == d_ker).all())
    assert int(st2["moe_poison"]) >= 0


# ---------------------------------------------------------------------------
# serving-semantics bugfixes
# ---------------------------------------------------------------------------


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=n).astype(np.int32) for n in lens]


def test_batching_invariance():
    """A batched left-padded wave must emit exactly the tokens each
    request would get served solo — pads are poisoned, not token 0."""
    eng = Engine(DENSE_CFG, slots=4, max_len=32)
    prompts = _prompts([3, 5, 7, 4], DENSE_CFG.vocab)
    batched = eng.run([Request(rid=i, prompt=p, max_new=4)
                       for i, p in enumerate(prompts)])
    solo_eng = Engine(DENSE_CFG, eng.params, slots=1, max_len=32)
    for i, p in enumerate(prompts):
        solo = solo_eng.run([Request(rid=0, prompt=p, max_new=4)])
        assert batched[i] == solo[0], (
            f"request {i} (len {len(p)}) diverged between batched and solo")


def test_batching_invariance_moe_engine():
    """The moe-family engine also pads safely: same wave, same result on
    repeat runs, and pad rows don't crash the dispatch path."""
    eng = Engine(CFG, slots=3, max_len=32, dispatch="spec-kernel")
    prompts = _prompts([4, 6, 5], CFG.vocab, seed=1)
    reqs = [Request(rid=i, prompt=p, max_new=3)
            for i, p in enumerate(prompts)]
    first = eng.run(reqs)
    again = eng.run([Request(rid=i, prompt=p, max_new=3)
                     for i, p in enumerate(prompts)])
    assert first == again


def test_truncation_is_explicit():
    """Hitting max_len with output budget left marks truncated=True and
    records a serve.truncate FailureEvent — never a silent cut."""
    eng = Engine(DENSE_CFG, slots=1, max_len=8)
    r = Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32), max_new=10)
    res = eng.run([r])
    assert r.truncated and r.done and not r.failed
    assert 0 < len(res[0]) < 10
    ev = [e for e in eng.events if e.site == "serve.truncate"]
    assert len(ev) == 1 and ev[0].outcome == "truncated"
    # a request that fits is NOT truncated
    eng2 = Engine(DENSE_CFG, eng.params, slots=1, max_len=32)
    r2 = Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32), max_new=4)
    eng2.run([r2])
    assert not r2.truncated and not eng2.events


def test_wave_stats_accounting():
    """WaveStats counts committed tokens and MoE dispatch requests
    exactly (prefill + one issue per decode call per token)."""
    eng = Engine(CFG, slots=2, max_len=32, dispatch="spec-kernel")
    prompts = _prompts([4, 4], CFG.vocab, seed=2)
    eng.run([Request(rid=i, prompt=p, max_new=3)
             for i, p in enumerate(prompts)])
    assert len(eng.wave_stats) == 1
    st = eng.wave_stats[0]
    assert st.batch == 2 and st.tokens == 6 and st.truncated == 0
    per_tok = eng._moe_per_tok
    assert per_tok > 0
    # prefill: 2 rows × 4 positions; decode: 3 calls × 2 rows
    assert st.moe_requests == (2 * 4 + 3 * 2) * per_tok
    assert 0 <= st.moe_poison <= st.moe_requests
    assert st.wall_s > 0


def test_served_wave_matches_hand_stepped_loop():
    """A served wave commits exactly the tokens, and counts exactly the
    poisoned dispatches, of a hand-stepped loop: prefill, then one decode
    call per step, the last step's included.  The engine reads each step's
    tokens in one transfer and the decode poison total once, at the
    wave's end; neither may change what it serves or counts."""
    # an expert holds at least 8 dispatches, so a decode step loses some
    # only with more rows than that routed to one of the 4 experts
    cfg = dataclasses.replace(CFG, capacity_factor=0.5)
    rows = 16
    eng = Engine(cfg, slots=rows, max_len=32, dispatch="spec-kernel")
    prompts = _prompts([4 + i % 5 for i in range(rows)], cfg.vocab, seed=4)
    max_new = [1 + i % 4 for i in range(rows)]
    reqs = [Request(rid=i, prompt=p, max_new=m)
            for i, (p, m) in enumerate(zip(prompts, max_new))]
    eng.run(reqs)
    (st,) = eng.wave_stats

    model, plen = eng.model, max(map(len, prompts))
    toks = np.zeros((len(prompts), plen), np.int32)
    pads = np.zeros((len(prompts),), np.int32)
    for i, p in enumerate(prompts):
        toks[i, plen - len(p):] = p
        pads[i] = plen - len(p)
    pads = jnp.asarray(pads)
    logits, cache, stats = model.prefill(eng.params, jnp.asarray(toks), 32,
                                         pad_lens=pads, return_stats=True)
    prefill_poison, poison = int(stats["moe_poison"]), 0
    decode = jax.jit(lambda p, c, t, n, pl: model.decode_step(
        p, c, t, n, pad_lens=pl, return_stats=True))
    want = [[] for _ in prompts]
    for step in range(max(max_new)):
        cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for i, m in enumerate(max_new):
            if step < m:
                want[i].append(int(cur[i, 0]))
        logits, cache, stats = decode(eng.params, cache, cur, plen + step,
                                      pads)
        poison += int(stats["moe_poison"])
    assert [r.out for r in reqs] == want
    assert st.tokens == sum(max_new)
    assert prefill_poison > 0 and poison > 0
    assert st.moe_poison == prefill_poison + poison


def test_traffic_report():
    """The traffic harness serves the whole trace and reduces to a
    coherent report; the request trace itself is deterministic."""
    tc = TrafficConfig(n_requests=6, rate=500.0, prompt_len=(4, 6),
                       max_new=(2, 3), seed=3)
    a, arr_a = make_requests(tc, CFG.vocab)
    b, arr_b = make_requests(tc, CFG.vocab)
    assert all((x.prompt == y.prompt).all() and x.max_new == y.max_new
               for x, y in zip(a, b))
    np.testing.assert_array_equal(arr_a, arr_b)

    eng = Engine(CFG, slots=4, max_len=32, dispatch="spec-kernel")
    rep = run_traffic(eng, tc)
    assert rep.n_completed == 6 and rep.n_failed == 0
    assert rep.p95_ms >= rep.p50_ms > 0
    assert rep.tokens > 0 and rep.tok_s > 0
    assert rep.moe_requests > 0 and 0 <= rep.poison_rate <= 1
    assert len(rep.latencies_ms) == 6
    assert sum(w.tokens for w in rep.waves) == rep.tokens


# ---------------------------------------------------------------------------
# chaos: the degradation ladder under traffic
# ---------------------------------------------------------------------------


def test_chaos_slot_death_contained():
    """serve.slot kills one request; the wave is never torn — survivors
    keep exactly their full output, the victim commits nothing."""
    eng = Engine(DENSE_CFG, slots=4, max_len=32, wave_retries=1)
    reqs = [Request(rid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts([4, 5, 4, 6], DENSE_CFG.vocab))]
    with faults.armed(FaultPlan({"serve.slot": 1.0}, seed=0, max_fires=1)):
        res = eng.run(reqs)
    failed = [r for r in reqs if r.failed]
    assert len(failed) == 1 and failed[0].out == []
    for r in reqs:
        if not r.failed:
            assert len(res[r.rid]) == 3, "survivor lost tokens"
    assert any(e.site == "serve.slot" and e.outcome == "failed"
               for e in eng.events)


def test_chaos_decode_timeout_retries_solo():
    """serve.decode tears the wave with no culprit: nothing commits from
    the torn wave, every request retries solo and completes clean."""
    eng = Engine(DENSE_CFG, slots=2, max_len=32, wave_retries=1)
    reqs = [Request(rid=i, prompt=p, max_new=3)
            for i, p in enumerate(_prompts([4, 5], DENSE_CFG.vocab))]
    with faults.armed(FaultPlan({"serve.decode": 1.0}, seed=0,
                                max_fires=1)):
        res = eng.run(reqs)
    assert all(not r.failed and len(res[r.rid]) == 3 for r in reqs), (
        "torn wave must not double or drop tokens")
    assert any(e.site == "serve.decode" and e.outcome == "retry"
               for e in eng.events)


def test_chaos_storm_shed_from_traffic():
    """serve.storm doubles the traffic with synthetic clones; they are
    served but shed — stats and results cover only real requests."""
    tc = TrafficConfig(n_requests=4, rate=500.0, prompt_len=(4, 5),
                       max_new=(2, 2), seed=5)
    eng = Engine(DENSE_CFG, slots=4, max_len=32)
    with faults.armed(FaultPlan({"serve.storm": 1.0}, seed=0,
                                max_fires=1)):
        rep = run_traffic(eng, tc)
    assert rep.n_completed == 4 and rep.n_failed == 0
    assert len(rep.latencies_ms) == 4
    assert rep.tokens == 4 * 2, "clone tokens must be shed from goodput"
    assert any(e.site == "serve.storm" and e.outcome == "shed"
               for e in eng.events)


# ---------------------------------------------------------------------------
# interpret-mode resolution regression (the jit-static default bug)
# ---------------------------------------------------------------------------


def _kernel_cases():
    from repro.kernels import flash_attention as fa
    from repro.kernels import paged_attention as pa
    from repro.kernels import ragged_matmul as rm
    q = jnp.zeros((1, 1, 16), jnp.float32)
    pages = jnp.zeros((1, 4, 1, 16), jnp.float32)
    pt = jnp.zeros((1, 1), jnp.int32)
    sl = jnp.ones((1,), jnp.int32)
    fq = jnp.zeros((1, 1, 8, 16), jnp.float32)
    x = jnp.zeros((8, 16), jnp.float32)
    w = jnp.zeros((1, 16, 16), jnp.float32)
    return [
        (pa, "_paged_attention",
         lambda **kw: pa.paged_attention(q, pages, pages, pt, sl, **kw)),
        (fa, "_flash_attention",
         lambda **kw: fa.flash_attention(fq, fq, fq, **kw)),
        (rm, "_ragged_matmul",
         lambda **kw: rm.ragged_matmul(x, w, capacity=8, **kw)),
    ]


@pytest.mark.parametrize("case", _kernel_cases(),
                         ids=["paged", "flash", "ragged"])
def test_interpret_resolved_per_call(case, monkeypatch):
    """The public wrappers resolve interpret OUTSIDE the jitted core: the
    env knob is read on every call, an explicit kwarg wins, and nothing
    is baked into a trace (the spy sees a fresh value each call)."""
    mod, core_name, call = case
    seen = []
    monkeypatch.setattr(mod, core_name,
                        lambda *a, **kw: seen.append(kw["interpret"]))
    monkeypatch.delenv("DAE_PALLAS_INTERPRET", raising=False)
    call()                                   # backend auto: CPU → interpret
    monkeypatch.setenv("DAE_PALLAS_INTERPRET", "0")
    call()                                   # env flips it per call...
    monkeypatch.setenv("DAE_PALLAS_INTERPRET", "1")
    call()
    call(interpret=False)                    # ...explicit kwarg beats env
    assert seen == [True, False, True, False]


def test_paged_attention_env_interpret_executes(monkeypatch):
    """DAE_PALLAS_INTERPRET=1 actually drives the kernel (not just the
    resolver) and matches the default CPU run."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 2, 16), jnp.float32)
    pages = jax.random.normal(key, (4, 4, 2, 16), jnp.float32)
    pt = jnp.array([[0, 1], [2, -1]], jnp.int32)
    sl = jnp.array([6, 3], jnp.int32)
    from repro.kernels.paged_attention import paged_attention
    monkeypatch.delenv("DAE_PALLAS_INTERPRET", raising=False)
    ref = paged_attention(q, pages, pages, pt, sl)
    monkeypatch.setenv("DAE_PALLAS_INTERPRET", "1")
    out = paged_attention(q, pages, pages, pt, sl)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))
