"""spec_gather — speculative row gather with poison (Pallas TPU).

The paper's DAE template mapped onto the TPU memory system:

* **AGU**: the row indices are *scalar-prefetched*
  (``PrefetchScalarGridSpec``) — the scalar core reads them ahead of the
  grid, so the DMA engine (the DU) issues HBM→VMEM row fetches ahead of
  compute.  A poisoned request (``idx < 0``) still fetches a (clamped)
  row — requests are speculative and never replayed.
* **CU**: the kernel body applies the poison mask, zeroing mis-speculated
  rows — the predicated-store/`store_inv` analogue (§3.1).

Block layout: grid ``(n // block_n, d // block_d)``; each step gathers a
``(block_n, block_d)`` tile.  The table stays un-blocked in ``ANY`` memory
space and the scalar-prefetched index drives a *burst* of ``block_n``
DMAs into a VMEM scratch (all started, then all awaited, so the copies
overlap), after which each request's row is picked out and the poison
mask applied.  A TPU DMA moves whole ``(8, 128)`` tiles of a 32-bit table
(``(16, 128)`` for 16-bit dtypes), so a single table row cannot be its
own copy: each request fetches the aligned *row block* of ``sub`` rows
that holds its row (:func:`sublanes`) and the kernel selects the row
inside VMEM.  Tables whose row count is not a multiple of ``sub`` are
padded.  The feature dim is tiled to keep the VMEM working set bounded for
wide rows.  ``n`` not divisible by ``block_n`` is handled by padding the
index vector with poison (``-1``) — padded rows fetch row 0 and mask to
zero, and the pad is sliced off the output.

Ragged-``n`` contract with the codegen backend: ``block_n`` is clamped to
``min(block_n, n)`` below, so a caller whose batch is smaller than its
requested block still lowers — but the epoch drivers
(:mod:`repro.codegen.epochs`) additionally floor their power-of-two batch
padding at ``max(8, block_n)``, so generated kernels never rely on this
clamp and every grid covers at least one full block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..resilience import faults
from .backend import resolve_interpret


def sublanes(dtype) -> int:
    """Rows in one native TPU tile of ``dtype``: 8 for 32-bit, 16 for
    16-bit, 32 for 8-bit — the granularity of a row-block DMA."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _kernel(idx_ref, table_ref, out_ref, blocks, sems, *, block_n, block_d,
            sub):
    nb = pl.program_id(0)
    j = pl.program_id(1)
    base = nb * block_n
    cols = pl.ds(pl.multiple_of(j * block_d, block_d), block_d)
    # burst: start all row-block DMAs, then wait — copies overlap in the
    # DMA engine (the multi-request window of the paper's DU)
    dmas = []
    for r in range(block_n):
        row = jnp.maximum(idx_ref[base + r], 0)
        start = pl.multiple_of(row // sub * sub, sub)
        dma = pltpu.make_async_copy(table_ref.at[pl.ds(start, sub), cols],
                                    blocks.at[r], sems.at[r])
        dma.start()
        dmas.append(dma)
    for dma in dmas:
        dma.wait()
    # pick each request's row out of its block: a max over the block with
    # every other row masked to the dtype's least value is exact for every
    # value (``-0.0`` included) and needs no dynamic sublane addressing,
    # which packed 16-bit tiles do not support
    dt = blocks.dtype
    least = (-jnp.inf if jnp.issubdtype(dt, jnp.floating)
             else jnp.iinfo(dt).min)
    block_rows = jax.lax.broadcasted_iota(jnp.int32, (sub, block_d), 0)
    for r in range(block_n):
        raw = idx_ref[base + r]
        hit = block_rows == jnp.maximum(raw, 0) % sub
        got = jnp.max(jnp.where(hit, blocks[r], least), axis=0,
                      keepdims=True)
        out_ref[pl.ds(r, 1), :] = jnp.where(raw < 0, jnp.zeros_like(got),
                                            got)


def spec_gather(table: jax.Array, idx: jax.Array, *, block_d: int = 512,
                block_n: int = 8, interpret: bool | None = None) -> jax.Array:
    """Gather ``table[idx]`` with poisoned (negative) indices zeroed.

    ``interpret`` pins the Pallas mode per call (None = backend policy,
    see :func:`repro.kernels.backend.resolve_interpret`).  Resolution
    happens *outside* the jitted core so the env knob is read per call,
    not baked into the first trace.

    Fault sites (active only under an armed
    :class:`~repro.resilience.faults.FaultPlan`; one bool check when
    unarmed): ``kernels.gather.allpoison`` poisons the whole request
    batch before the kernel, ``kernels.gather.rows`` corrupts alternate
    output rows after it.  Both are *detectable* corruptions — the
    codegen drivers verify gathers against an independent host replica
    and refuse to commit downstream values.
    """
    if faults.ACTIVE and faults.fire("kernels.gather.allpoison"):
        idx = jnp.full_like(idx, -1)
    out = _spec_gather(table, idx, block_d=block_d, block_n=block_n,
                       interpret=resolve_interpret(interpret))
    if faults.ACTIVE and faults.fire("kernels.gather.rows"):
        out = out.at[::2].add(jnp.ones((), out.dtype))
    return out


@functools.partial(jax.jit,
                   static_argnames=("block_d", "block_n", "interpret"))
def _spec_gather(table: jax.Array, idx: jax.Array, *, block_d: int,
                 block_n: int, interpret: bool) -> jax.Array:
    n = idx.shape[0]
    v, d = table.shape
    bd = min(block_d, d)
    bn = min(block_n, n)
    sub = sublanes(table.dtype)
    assert d % bd == 0, f"feature dim {d} not divisible by block {bd}"

    if v % sub:
        table = jnp.pad(table, ((0, -v % sub), (0, 0)))
    pad = (-n) % bn
    if pad:
        idx = jnp.concatenate([idx, jnp.full((pad,), -1, idx.dtype)])
    np_ = n + pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // bn, d // bd),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((bn, bd), lambda i, j, idx_ref: (i, j)),
        scratch_shapes=[pltpu.VMEM((bn, sub, bd), table.dtype),
                        pltpu.SemaphoreType.DMA((bn,))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_n=bn, block_d=bd, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, d), table.dtype),
        interpret=interpret,
    )(idx, table)
    return out[:n] if pad else out
