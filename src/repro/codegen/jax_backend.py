"""jax target: drive the generated CU through the real Pallas kernel layer.

The decoupled arrays live on device as lane-dense int32 tables
(:class:`DeviceTable`); the generated CU
(:func:`repro.codegen.emit.compile_mode` in ``cu-jax`` mode) runs as a
host-side generator that *yields* an array name whenever its
load-value buffer runs dry.  On each yield the driver

1. **flushes** every store value the CU has produced for that array —
   poisoned slots become ``-1`` indices, which is exactly the
   pad-with-poison path of :func:`repro.kernels.spec_scatter.
   spec_scatter_add` (dropped at commit, no out-of-bounds write); an
   overwrite store lowers to gather-current + scatter-add of the delta,
   which is bit-exact in two's-complement integer arithmetic; write-
   after-write collisions split the flush so in-order commit is preserved;
2. **refills** the buffer with the next *epoch* of load values via
   :func:`repro.kernels.spec_gather.spec_gather`: the epoch extends from
   the next unconsumed load up to (but excluding) the first load whose raw
   address aliases a still-unflushed store request — the host-side
   re-statement of the LSQ's dynamic disambiguation, computable ahead of
   time because the AGU stream already fixed every address.

Gather/scatter batches are padded to power-of-two buckets (pad indices are
poison) so the jitted kernels retrace a bounded number of shapes.

Subset rules (anything else raises ``CodegenError`` and the caller falls
back): decoupled arrays must be integer-typed with all values — initial
and produced — representable in int32.  Within that range the delta trick
and the int32 device arithmetic are exact, so final memory is bit-identical
to the sequential interpreter.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..resilience import faults
from ..resilience.faults import FaultDetected
from .analysis import CodegenError
from .emit import compile_mode
from .epochs import I32_MAX as _I32_MAX
from .epochs import I32_MIN as _I32_MIN
from .epochs import MAX_BATCH, bucket, gather_limit
from .streams import Streams


def _check_i32(name: str, arr: np.ndarray) -> None:
    if arr.dtype.kind not in "iu":
        raise CodegenError(
            f"jax target: decoupled array {name} has non-integer dtype "
            f"{arr.dtype}")
    if arr.size and (int(arr.min()) < _I32_MIN or int(arr.max()) > _I32_MAX):
        raise CodegenError(
            f"jax target: {name} holds values outside int32 range")


#: lanes of one TPU vector register row — the width of a device-table row
LANES = 128


@jax.jit
def _pick(rows: jax.Array, idx: jax.Array) -> jax.Array:
    """Element ``idx[k]`` out of its gathered row ``rows[k]``."""
    return jnp.take_along_axis(rows, (idx & (LANES - 1))[:, None], 1)[:, 0]


@jax.jit
def _spread(idx: jax.Array, vals: jax.Array) -> jax.Array:
    """One row per request: ``vals[k]`` at ``idx[k]``'s lane, zeros
    elsewhere (adding a zero leaves an int32 element bit-unchanged)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], LANES), 1)
    return jnp.where(lanes == (idx & (LANES - 1))[:, None], vals[:, None], 0)


class DeviceTable:
    """A flat int32 array on device, lane-dense: element ``i`` lives at
    row ``i // 128``, lane ``i % 128`` of a ``(rows, 128)`` table.

    A TPU DMA moves whole ``(8, 128)`` tiles, so the kernels cannot copy
    a one-lane row of an ``(n, 1)`` table, and padding every element to a
    full row would cost 128x the memory.  Packing 128 elements per row
    keeps the footprint of the flat array; a gather fetches the element's
    row and picks the lane, a scatter-add adds a row that is zero outside
    the element's lane — exact in int32, so results stay bit-identical.
    Indices are flat element indices; ``-1`` is poison, as in the kernels.
    """

    def __init__(self, flat: np.ndarray, block_n: int, interpret):
        self.n = len(flat)
        rows = -(-max(self.n, 1) // LANES)
        rows += -rows % 8                 # whole (8, 128) int32 tiles
        buf = np.zeros(rows * LANES, np.int32)
        buf[:self.n] = flat
        self.table = jnp.asarray(buf.reshape(rows, LANES))
        self.block_n = block_n
        self.interpret = interpret

    def gather(self, idx: np.ndarray) -> jax.Array:
        """``flat[idx]`` with poisoned (``-1``) requests reading 0."""
        from ..kernels.spec_gather import spec_gather
        rows = spec_gather(self.table, jnp.asarray(idx // LANES),
                           block_d=LANES, block_n=self.block_n,
                           interpret=self.interpret)
        return _pick(rows, jnp.asarray(idx))

    def scatter_add(self, idx: np.ndarray, vals) -> None:
        """``flat[idx] += vals`` with poisoned requests dropped."""
        from ..kernels.spec_scatter import spec_scatter_add
        self.table = spec_scatter_add(
            self.table, jnp.asarray(idx // LANES),
            _spread(jnp.asarray(idx), jnp.asarray(vals, jnp.int32)),
            block_d=LANES, block_n=self.block_n, interpret=self.interpret)

    def to_numpy(self) -> np.ndarray:
        """The flat array, back on the host."""
        return np.asarray(self.table).reshape(-1)[:self.n]


class _ArrayDriver:
    """Epoch scheduler for one decoupled array."""

    def __init__(self, name: str, mem: np.ndarray, streams: Streams,
                 block_n: int, interpret):
        self.name = name
        self.dtype = mem.dtype
        self.hi = len(mem) - 1
        self.table = DeviceTable(mem.astype(np.int32), block_n, interpret)
        # shadow replica of the device table, kept only when the armed
        # plan can silently corrupt data (see faults.CORRUPTION_SITES):
        # exact by induction (only these flushes mutate the table), so
        # any divergence is detected corruption.  None otherwise — the
        # hot path keeps zero copies.
        self.shadow = mem.astype(np.int32) if faults.corrupting() else None
        self.ld_clamped = streams.ld_clamped.get(name, [])
        self.ld_raw = streams.ld_raw.get(name, [])
        self.ld_pos = streams.ld_pos.get(name, [])
        self.st_addrs = streams.st_addrs.get(name, [])
        self.st_pos = streams.st_pos.get(name, [])
        self.lp = 0          # next unconsumed load index
        self.fp = 0          # flushed store count
        self.block_n = block_n
        self.gather_calls = 0
        self.scatter_calls = 0

    # -- store flush ---------------------------------------------------------
    def flush(self, produced: list) -> None:
        """Apply ``produced`` (values / POISON sentinels) in commit order."""
        from ..core.sim.base import POISON
        faults.inject("codegen.jax.flush")
        if not produced:
            return
        if self.fp + len(produced) > len(self.st_addrs):
            raise CodegenError(f"store stream underrun @{self.name}")
        addrs = self.st_addrs[self.fp:self.fp + len(produced)]
        idx_b: list = []
        val_b: list = []
        seen = set()
        for a, v in zip(addrs, produced):
            poison = v is POISON
            if len(idx_b) >= MAX_BATCH or (not poison and a in seen):
                self._scatter(idx_b, val_b)
                idx_b, val_b, seen = [], [], set()
            if poison:
                idx_b.append(-1)
                val_b.append(0)
                continue
            if not (0 <= a <= self.hi):
                raise CodegenError(
                    f"non-poisoned store out of bounds: {self.name}[{a}]")
            iv = int(v)
            if iv < _I32_MIN or iv > _I32_MAX:
                raise CodegenError(
                    f"jax target: store value outside int32 range "
                    f"@{self.name}")
            seen.add(a)
            idx_b.append(a)
            val_b.append(iv)
        if idx_b:
            self._scatter(idx_b, val_b)
        self.fp += len(produced)
        del produced[:]

    def _scatter(self, idx_list: list, val_list: list) -> None:
        n = len(idx_list)
        b = bucket(n, self.block_n)
        idx = np.full(b, -1, np.int32)
        idx[:n] = idx_list
        vals = np.zeros(b, np.int32)
        vals[:n] = val_list
        cur = self.table.gather(idx)
        self.table.scatter_add(idx, jnp.where(idx >= 0, vals - cur, 0))
        self.gather_calls += 1
        self.scatter_calls += 1
        if self.shadow is not None:
            # flush splits batches on duplicate addresses, so zip order
            # here is commit order
            for a, v in zip(idx_list, val_list):
                if a >= 0:
                    self.shadow[a] = v

    # -- load refill ---------------------------------------------------------
    def refill(self, buf: deque) -> int:
        """Gather the next epoch of load values into ``buf``."""
        faults.inject("codegen.jax.refill")
        lds = self.ld_clamped
        if self.lp >= len(lds):
            return 0
        # epoch boundary (shared scheduler, pessimistic fence): stop
        # before the first load whose raw address aliases an unflushed
        # (>= fp) store request that is older in the combined stream —
        # its value must come through a flush first
        k = gather_limit(self.ld_raw, self.ld_pos, self.st_addrs,
                         self.st_pos, self.lp, self.fp)
        take = lds[self.lp:k]
        if not take:
            return 0
        n = len(take)
        b = bucket(n, self.block_n)
        idx = np.full(b, -1, np.int32)
        idx[:n] = take
        vals = self.table.gather(idx)
        self.gather_calls += 1
        got = np.asarray(vals[:n])
        if self.shadow is not None:
            exp = self.shadow[np.asarray(take, dtype=np.int64)]
            if not np.array_equal(got, exp):
                raise FaultDetected(
                    "codegen.jax.refill",
                    f"gather verify failed @{self.name}: device rows "
                    f"differ from shadow replica")
        buf.extend(int(x) for x in got)
        self.lp = k
        return n


def run_jax(compiled, memory: Dict[str, np.ndarray],
            params: Dict[str, Any], streams: Streams, analysis,
            *, interpret: Optional[bool] = None, block_n: int = 8,
            max_steps: int = 2_000_000) -> Dict[str, Any]:
    """Execute the CU against device tables; mutates ``memory`` on success.

    Raises :class:`CodegenError` (memory untouched) when the run leaves
    the supported subset — the caller decides whether to fall back.
    """
    cu_make = compile_mode(compiled.cu, "cu-jax")
    if cu_make is None:
        raise CodegenError("CU slice not lowerable for the jax target")

    dec = sorted(set(streams.arrays) | set(analysis.decoupled))
    for a in dec:
        _check_i32(a, memory[a])

    drivers = {a: _ArrayDriver(a, memory[a], streams, block_n, interpret)
               for a in dec}
    bufs: Dict[str, deque] = {a: deque() for a in dec}
    outs: Dict[str, list] = {a: [] for a in dec}
    stats: Dict[str, Any] = {}

    gen = cu_make(memory, dict(params), bufs, outs, stats, max_steps)
    while True:
        try:
            arr = next(gen)
        except StopIteration:
            break
        drv = drivers[arr]
        drv.flush(outs[arr])
        if drv.refill(bufs[arr]) == 0:
            raise CodegenError(
                f"jax target: CU blocked on {arr} but no gatherable loads "
                f"remain (stream mismatch)")
    for a in dec:  # drain store values produced after the last consume
        drivers[a].flush(outs[a])

    # integrity barrier: before the first write to caller memory, every
    # device table must agree with its shadow replica (armed runs only —
    # a scatter that dropped or corrupted committed stores is caught
    # here at the latest, never committed)
    for a in dec:
        drv = drivers[a]
        if drv.shadow is not None:
            tab = drv.table.to_numpy()
            if not np.array_equal(tab, drv.shadow):
                raise FaultDetected(
                    "codegen.jax.commit",
                    f"device table for {a} diverged from shadow replica")

    # every flush succeeded — only now touch the caller's memory (the CU
    # epilogue deliberately left its local-array mirrors in stats)
    for a, mirror in stats.pop("locals", {}).items():
        memory[a][:] = mirror
    for a in dec:
        tab = drivers[a].table.to_numpy().astype(memory[a].dtype)
        memory[a][:] = tab
    stats["gather_calls"] = sum(d.gather_calls for d in drivers.values())
    stats["scatter_calls"] = sum(d.scatter_calls for d in drivers.values())
    # leftover contract (same meaning on every path, incl. the coupled
    # interpreter and the vectorised CU): requests the AGU issued that the
    # CU never consumed/valued — legitimate speculative over-issue past CU
    # exit.  Values gathered into a buffer but never popped still count.
    stats["ld_leftover"] = sum(len(d.ld_clamped) - d.lp + len(bufs[a])
                               for a, d in drivers.items())
    stats["st_leftover"] = sum(len(d.st_addrs) - d.fp
                               for d in drivers.values())
    return stats
