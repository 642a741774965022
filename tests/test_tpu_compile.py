"""Compile the main path's kernels for a TPU v5e that is described, not
attached — the no-chip guard of the device path.

Nothing here runs: each test lowers and compiles for one chip of a
described ``v5e:2x2`` topology (four for the expert-parallel layer) and
asserts that the Pallas kernel survived as a ``tpu_custom_call``.  The
compiler refuses here what interpret mode accepts (unaligned DMA slices,
vector reads of scalar memory), so a kernel change that would crash on
the chip fails in the tier-1 run instead.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles (entries written for a described chip cannot be read back).
"""
from __future__ import annotations

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

#: Kimi K2 at full widths on one chip, cut as in ``chip_smoke.py``: 16
#: experts, a 4 x 512-token prefill wave (2048 tokens, 16384 dispatches)
D_MODEL, EXPERT_FF, TOP_K, N_EXPERTS, TOKENS = 7168, 2048, 8, 16, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_kernel_named(compiled, kernel):
    """Every Pallas kernel of ``compiled`` keeps ``kernel`` in the name a
    profiler trace gives it (its HLO line, read by
    ``bench/lib/trace.op_name``), which the roofline readers match."""
    from bench.lib.trace import op_name
    names = [op_name(line.strip().removeprefix("ROOT "))
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert names and all(kernel in n for n in names), names


def _moe_capacity():
    from repro.models.moe import round_capacity
    return round_capacity(TOKENS, N_EXPERTS, TOP_K, 1.25)


#: (table shape, dtype, requests, block_d): the codegen jax target's
#: lane-dense int32 table (``repro.codegen.jax_backend.DeviceTable``) at
#: the epoch planner's largest batch, and the MoE expert buffer at its
#: real width in the serving dtype and in float32
KERNEL_SHAPES = {
    "codegen-int32": (lambda: (8192, 128), jnp.int32, 512, 128),
    "moe-bf16": (lambda: (N_EXPERTS * _moe_capacity(), D_MODEL),
                 jnp.bfloat16, TOKENS * TOP_K, 512),
    "moe-f32": (lambda: (N_EXPERTS * _moe_capacity(), D_MODEL),
                jnp.float32, TOKENS * TOP_K, 512),
}


@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_spec_gather_compiles(one_chip, case):
    from repro.kernels.spec_gather import _spec_gather
    shape, dtype, n, bd = KERNEL_SHAPES[case]
    fn = functools.partial(_spec_gather, block_d=bd, block_n=8,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _spec(shape(), dtype, one_chip),
        _spec((n,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
    _assert_kernel_named(compiled, "spec_gather")


@pytest.mark.parametrize("case", sorted(KERNEL_SHAPES))
def test_spec_scatter_add_compiles(one_chip, case):
    from repro.kernels.spec_scatter import _spec_scatter_add
    shape, dtype, n, bd = KERNEL_SHAPES[case]
    fn = functools.partial(_spec_scatter_add, block_d=bd, block_n=8,
                           interpret=False)
    compiled = jax.jit(fn).lower(
        _spec(shape(), dtype, one_chip),
        _spec((n,), jnp.int32, one_chip),
        _spec((n, shape()[1]), dtype, one_chip)).compile()
    _assert_kernel(compiled)
    _assert_kernel_named(compiled, "spec_scatter_add")


def _moe_params(dtype, n_experts, expert, other):
    """Parameter shapes of one MoE layer with a shared expert."""
    d, f = D_MODEL, EXPERT_FF
    return {"router": _spec((d, n_experts), dtype, other),
            "w_gate": _spec((n_experts, d, f), dtype, expert),
            "w_up": _spec((n_experts, d, f), dtype, expert),
            "w_down": _spec((n_experts, f, d), dtype, expert),
            "shared_w_gate": _spec((d, f), dtype, other),
            "shared_w_up": _spec((d, f), dtype, other),
            "shared_w_down": _spec((f, d), dtype, other)}


def test_moe_layer_compiles(one_chip, monkeypatch):
    """One spec-kernel MoE layer of the serve phase's prefill wave."""
    from repro.models import moe
    # the kernels resolve interpret mode from the (CPU) backend; pin
    # compiled Pallas as the chip would
    monkeypatch.setenv("DAE_PALLAS_INTERPRET", "0")
    fn = functools.partial(moe.moe_spec, n_experts=N_EXPERTS, top_k=TOP_K,
                           capacity_factor=1.25, kernel=True, stats=True)
    _assert_kernel(jax.jit(fn).lower(
        _moe_params(jnp.bfloat16, N_EXPERTS, one_chip, one_chip),
        _spec((TOKENS, D_MODEL), jnp.bfloat16, one_chip)).compile())


def test_moe_expert_parallel_compiles(topo, monkeypatch):
    """The four-chip phase's layer: 32 experts sharded over ``model``."""
    from repro.models import moe
    monkeypatch.setenv("DAE_PALLAS_INTERPRET", "0")
    mesh = jax.sharding.Mesh(
        np.array(topo.devices).reshape(1, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = _moe_params(jnp.float32, 32,
                         NamedSharding(mesh, P("model", None, None)),
                         NamedSharding(mesh, P()))
    fn = functools.partial(moe.moe_spec, n_experts=32, top_k=TOP_K,
                           capacity_factor=1.0, kernel=True, stats=True)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(
            params, _spec((TOKENS, D_MODEL), jnp.float32,
                          NamedSharding(mesh, P()))).compile()
    _assert_kernel(compiled)
    assert "all-reduce" in compiled.as_text()  # the EP combine's psum
