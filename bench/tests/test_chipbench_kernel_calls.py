"""The program issues the speculative kernel calls that the configuration
logs for a wave: the engine's prefill and jitted decode are traced to
jaxprs, and the ``_spec_gather`` and ``_spec_scatter_add`` kernels in
them, counted through every layer scan, are compared with the
configuration's ``kernel_calls``, shape by shape.  A traced chip run
fails where the two disagree; this finds it on the CPU first."""
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

import tiny
from bench.lib import spec

#: the kernels' jitted entry points, by the names the trace gives them
KERNELS = {"_spec_gather": "spec_gather",
           "_spec_scatter_add": "spec_scatter_add"}
SHAPE = ("kernel", "requests", "d", "itemsize", "table_rows")


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for s in v if isinstance(v, (list, tuple)) else (v,):
            if isinstance(s, jax.extend.core.ClosedJaxpr):
                yield s.jaxpr
            elif isinstance(s, jax.extend.core.Jaxpr):
                yield s


def _has_pallas(jaxpr) -> bool:
    return any(e.primitive.name == "pallas_call" or
               any(_has_pallas(j) for j in _subjaxprs(e))
               for e in jaxpr.eqns)


def _kernel_calls(jaxpr, times: int, found: Counter) -> None:
    """Adds each kernel call of ``jaxpr``, run ``times`` times, to
    ``found`` by its shape: ``table`` and ``idx`` are the entry points'
    first two operands."""
    for e in jaxpr.eqns:
        name = e.params.get("name")
        if name in KERNELS and _has_pallas(e.params["jaxpr"].jaxpr):
            table, idx = (v.aval for v in e.invars[:2])
            found[(KERNELS[name], idx.shape[0], table.shape[1],
                   table.dtype.itemsize, table.shape[0])] += times
            continue
        subs = list(_subjaxprs(e))
        if e.primitive.name in ("while", "cond") and any(
                _has_pallas(j) for j in subs):
            raise AssertionError(f"a kernel under {e.primitive.name}: the "
                                 "count cannot follow it")
        n = times * e.params["length"] if e.primitive.name == "scan" \
            else times
        for j in subs:
            _kernel_calls(j, n, found)


def _logged(calls) -> Counter:
    out = Counter()
    for c in calls:
        out[tuple(c[k] for k in SHAPE)] += c["count"]
    return out


def _cells():
    tiny_cfg = tiny.serve_cell().config
    real = spec.load_cell("kimi_k2_ep24.decode_heavy").config
    return {"tiny": (tiny_cfg, 4, 12, 7), "kimi_k2_ep24": (real, 32, 512,
                                                           511)}


@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("cell", ["tiny", "kimi_k2_ep24"])
def test_program_issues_the_logged_kernel_calls(cell, phase):
    """Traced with the weights' shapes alone, so the real cell's widths
    cost no memory."""
    from repro.serve.engine import Engine

    cfg, rows, plen, decode_calls = _cells()[cell]
    ref = spec.load_module("configs", "kimi_k2_ep24")
    arch = spec.load_module("drivers", "serve").program_arch(cfg, ref)
    params = jax.eval_shape(ref.params_fn(cfg), jax.random.PRNGKey(0))
    eng = Engine(arch, params, slots=rows, max_len=plen + decode_calls + 1,
                 dispatch=cfg["assumed"]["dispatch"])
    toks = jax.ShapeDtypeStruct((rows, plen), jnp.int32)
    pads = jax.ShapeDtypeStruct((rows,), jnp.int32)

    def prefill(p, t, pl):
        return eng.model.prefill(p, t, max_len=eng.max_len, pad_lens=pl,
                                 return_stats=True)

    found = Counter()
    before = _logged(ref.kernel_calls(cfg, rows, plen, 0))
    if phase == "prefill":
        _kernel_calls(jax.make_jaxpr(prefill)(params, toks, pads).jaxpr, 1,
                      found)
        assert found == before
    else:
        _, cache, _ = jax.eval_shape(prefill, params, toks, pads)
        cur = jax.ShapeDtypeStruct((rows, 1), jnp.int32)
        jaxpr = jax.make_jaxpr(eng._decode)(params, cache, cur, plen, pads)
        _kernel_calls(jaxpr.jaxpr, decode_calls, found)
        assert found == _logged(ref.kernel_calls(cfg, rows, plen,
                                                 decode_calls)) - before
    assert found


def test_a_file_the_program_does_not_run_is_refused_before_the_weights(
        monkeypatch):
    cell = tiny.serve_cell()
    cell.config["scoring_func"] = "sigmoid"
    load = spec.load_module

    def no_weights(kind, name):
        mod = load(kind, name)
        if kind == "configs":
            def make_params(cfg, seed):
                raise AssertionError("weights drawn")
            mod.make_params = make_params
        return mod

    monkeypatch.setattr(spec, "load_module", no_weights)
    with pytest.raises(ValueError, match="scoring_func"):
        tiny.run(cell, {})
