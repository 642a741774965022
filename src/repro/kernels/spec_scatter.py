"""spec_scatter — poison-masked scatter-add (Pallas TPU).

The predicated-store half of the paper's architecture (§3.1): every store
request reaches the memory system (speculation), but a poisoned request
(``idx < 0``) is **dropped at commit** — the destination row is never
touched.  No replay, no out-of-bounds commit: poisoned indices clamp to
row 0 for the speculative fetch and contribute zero.

Implementation: grid ``(d // block_d, n // block_n)`` with the request dim
fast; each step handles a *block* of ``block_n`` destination-sorted
requests.  The table (aliased as the output) stays un-blocked in ``ANY``
memory space; per request the kernel DMAs the aligned row block holding
the destination row (``sub`` rows, one native tile —
:func:`repro.kernels.spec_gather.sublanes`; a TPU DMA cannot move a single
row of a tiled table) into VMEM, adds the (poison-masked) contribution to
that one row, and DMAs the block back — the scalar-prefetched index drives
the row selection, and the read-modify-write chain through VMEM keeps
same-block runs of the sorted requests coherent.  Rows the request does
not address are written back bit-unchanged.  Tables whose row count is not
a multiple of ``sub`` are padded.  ``n`` not divisible by ``block_n`` pads
the request vector with poison (contributes nothing, by construction).

Ragged-``n`` contract with the codegen backend: ``block_n`` is clamped to
``min(block_n, n)`` below, but the epoch drivers
(:mod:`repro.codegen.epochs`) floor their power-of-two batch padding at
``max(8, block_n)``, so generated kernels hand this kernel full blocks; a
poisoned (``-1``) slot — pad, dropped store, or WAW-superseded write —
reads and re-writes row 0's slice unchanged (contribution is zeroed), so
over-padding is safe, not just tolerated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..resilience import faults
from .backend import resolve_interpret
from .spec_gather import sublanes


def _kernel(idx_ref, vals_ref, table_ref, out_ref, blk, sem, *,
            block_n, block_d, sub):
    j = pl.program_id(0)
    nb = pl.program_id(1)
    base = nb * block_n
    cols = pl.ds(pl.multiple_of(j * block_d, block_d), block_d)
    block_rows = jax.lax.broadcasted_iota(jnp.int32, (sub, block_d), 0)
    for r in range(block_n):
        raw = idx_ref[base + r]
        row = jnp.maximum(raw, 0)
        start = pl.multiple_of(row // sub * sub, sub)
        window = out_ref.at[pl.ds(start, sub), cols]
        rd = pltpu.make_async_copy(window, blk, sem)
        rd.start()
        rd.wait()
        hit = (block_rows == row - start) & (raw >= 0)
        cur = blk[...]
        blk[...] = jnp.where(hit, cur + vals_ref[pl.ds(r, 1), :], cur)
        wr = pltpu.make_async_copy(blk, window, sem)
        wr.start()
        wr.wait()


def spec_scatter_add(table: jax.Array, idx: jax.Array, values: jax.Array, *,
                     block_d: int = 512, block_n: int = 8,
                     interpret: bool | None = None) -> jax.Array:
    """Return table with ``values`` added at ``idx`` (poisoned rows dropped).

    Requests are destination-sorted inside the wrapper (MoE combines arrive
    expert-contiguous already — the AGU's topological-order discipline,
    §5.1.3 — making the sort a no-op there).

    ``interpret`` pins the Pallas mode per call (None = backend policy,
    see :func:`repro.kernels.backend.resolve_interpret`).  Resolution
    happens *outside* the jitted core so the env knob is read per call,
    not baked into the first trace.

    Fault sites (active only under an armed
    :class:`~repro.resilience.faults.FaultPlan`):
    ``kernels.scatter.raise`` raises mid-epoch before the kernel;
    ``kernels.scatter.allpoison`` silently drops the whole batch
    (every index poisoned) — the codegen drivers' shadow replicas catch
    the missing commits before memory write-back.
    """
    if faults.ACTIVE:
        faults.inject("kernels.scatter.raise")
        if faults.fire("kernels.scatter.allpoison"):
            idx = jnp.full_like(idx, -1)
    return _spec_scatter_add(table, idx, values, block_d=block_d,
                             block_n=block_n,
                             interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("block_d", "block_n", "interpret"))
def _spec_scatter_add(table: jax.Array, idx: jax.Array, values: jax.Array, *,
                      block_d: int, block_n: int,
                      interpret: bool) -> jax.Array:
    n = idx.shape[0]
    v, d = table.shape
    bd = min(block_d, d)
    bn = min(block_n, n)
    sub = sublanes(table.dtype)
    assert d % bd == 0

    order = jnp.argsort(idx)
    idx = idx[order]
    values = values[order]

    vp = v + (-v % sub)
    if vp != v:
        table = jnp.pad(table, ((0, vp - v), (0, 0)))
    pad = (-n) % bn
    if pad:
        idx = jnp.concatenate([idx, jnp.full((pad,), -1, idx.dtype)])
        values = jnp.concatenate(
            [values, jnp.zeros((pad, d), values.dtype)])
    np_ = n + pad

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bd, np_ // bn),
        in_specs=[
            pl.BlockSpec((bn, bd), lambda j, i, idx_ref: (i, j)),  # values
            pl.BlockSpec(memory_space=pl.ANY),                     # table
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((sub, bd), table.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_n=bn, block_d=bd, sub=sub),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((vp, d), table.dtype),
        input_output_aliases={2: 0},  # table aliases the output (index
                                      # counts the scalar-prefetch operand)
        interpret=interpret,
    )(idx, values, table)
    return out[:v] if vp != v else out
