"""Mixture-of-Experts with **speculative DAE dispatch** — the paper's
technique as a first-class model feature (DESIGN.md §3).

Whether token *t*'s activations are stored into expert *e*'s buffer is
control-dependent on ``top_k(router(x))`` — a §4 control LoD.  Two paths:

* ``dispatch="spec"`` (default, the paper / Fig. 1c): every token issues its
  store into a **fixed-capacity** per-expert buffer unconditionally
  (Algorithm 1's hoist — the request set is a shape-stable superset); tokens
  that lose the capacity race get their slot index **poisoned** (-1) and are
  dropped at commit, never replayed.  Combine gathers back with poisoned
  slots contributing zero.  Capacity overflow *is* the mis-speculation, and
  the cost is rate-independent by construction (Table-2's property).
* ``dispatch="dense"`` (the STA / if-conversion baseline): every token runs
  through **all** experts and results are gated — no speculation, E/top_k×
  the FLOPs.  This is what benchmarks/moe_ab.py compares against.
* ``dispatch="spec-kernel"`` (``kernel=True`` here): the same speculative
  slot assignment, but the buffer fill and the combine run through the
  paper's Pallas kernels — :func:`repro.kernels.spec_scatter.spec_scatter_add`
  commits the dispatch stores (poisoned slot = ``-1`` index, the kernels'
  pad-with-poison path) and :func:`repro.kernels.spec_gather.spec_gather`
  gathers the combine.  Bit-identical to the lax-scatter path by
  construction (each non-poisoned slot receives exactly one token), which
  is what ``tests/test_moe_serve.py`` pins — the lax path stays as the
  differential reference.

The buffers are expert-contiguous with capacity a multiple of the GEMM
tile; today the expert FFN runs as a batched einsum over the buffer (the
``ragged_matmul`` tiling is the planned TPU fast path for it, not what
executes here yet).

``stats=True`` additionally returns the number of **poisoned dispatch
requests** — capacity overflow, plus non-resident experts under the
expert-parallel mesh variant — as a traced int32 scalar, so the serving
engine can report exact per-wave mis-speculation rates.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.spec_gather import spec_gather
from ..kernels.spec_scatter import spec_scatter_add
from .sharding import _current_mesh


def round_capacity(n_tokens: int, n_experts: int, top_k: int,
                   factor: float, multiple: int = 8) -> int:
    cap = int(factor * n_tokens * top_k / n_experts) + 1
    return max(multiple, ((cap + multiple - 1) // multiple) * multiple)


def spec_dispatch_indices(gates: jax.Array, experts: jax.Array,
                          capacity: int, n_experts: int
                          ) -> Tuple[jax.Array, jax.Array]:
    """AGU slice: speculative slot assignment.

    gates/experts: (N, K).  Returns (slot_idx, gates) where slot_idx (N, K)
    is ``expert*capacity + position`` or **-1 (poison)** when the position
    exceeds capacity.  Pure index arithmetic — no data-dependent shapes.
    """
    n, k = experts.shape
    flat_e = experts.reshape(-1)                       # (N*K,) request order
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot     # 1-based position
    pos = (pos_in_e.sum(axis=-1) - 1).reshape(n, k)
    slot = experts * capacity + pos
    poison = pos >= capacity
    slot = jnp.where(poison, -1, slot)
    return slot, jnp.where(poison, 0.0, gates)


def _combine(h: jax.Array, slot: jax.Array, gates: jax.Array, *,
             kernel: bool) -> jax.Array:
    """Combine: gather each request's expert output row back (poisoned
    slots read zero) and sum a token's top-k rows weighted by ``gates``.

    The gathered rows pass an optimisation barrier on both paths, so XLA
    cannot fuse the lax gather into the weighted sum — a fusion the
    opaque kernel call never gets, and one that lets the compiler round
    the sum differently (a fused multiply-add), breaking bit-identity.
    """
    n, top_k = gates.shape
    if kernel:
        gathered = spec_gather(h, slot)
    else:
        gathered = jnp.where((slot < 0)[:, None],
                             jnp.zeros((1, h.shape[1]), h.dtype),
                             h[jnp.maximum(slot, 0)])
    gathered = jax.lax.optimization_barrier(gathered)
    return (gathered.reshape(n, top_k, -1)
            * gates[..., None].astype(h.dtype)).sum(axis=1)


def moe_spec(params: Dict, x: jax.Array, *, n_experts: int, top_k: int,
             capacity_factor: float, kernel: bool = False,
             stats: bool = False):
    """Speculative MoE layer.  x: (N, d) → (N, d).

    Under a mesh whose ``model`` axis divides the expert count, dispatch
    runs **expert-parallel** via shard_map (§Perf H2): every device routes
    its own tokens against ALL experts, poisons the requests whose expert is
    not resident locally (remote experts = mis-speculations, dropped not
    replayed), computes its local expert FFNs, and one psum over ``model``
    combines — no buffer gathers at all.

    ``kernel=True`` runs the buffer fill / combine through
    :func:`~repro.kernels.spec_scatter.spec_scatter_add` and
    :func:`~repro.kernels.spec_gather.spec_gather` (the ``spec-kernel``
    dispatch mode); ``stats=True`` returns ``(out, poisoned)`` where
    ``poisoned`` is the global int32 count of poisoned dispatch requests
    out of ``N * top_k`` (capacity overflow; identical across mesh
    variants because a request commits on exactly one device).
    """
    mesh = _current_mesh()
    ff = params["w_gate"].shape[-1]
    if (mesh is not None and "model" in mesh.axis_names
            and x.shape[0] % _dp_size(mesh) == 0):
        if n_experts % mesh.shape["model"] == 0:
            return _moe_spec_ep(params, x, n_experts=n_experts, top_k=top_k,
                                capacity_factor=capacity_factor, mesh=mesh,
                                kernel=kernel, stats=stats)
        if ff % mesh.shape["model"] == 0:
            # few experts (grok: 8 < 16 shards): replicate experts, TP the
            # expert FFN width, dispatch locally per device (§Perf H3)
            return _moe_spec_tp(params, x, n_experts=n_experts, top_k=top_k,
                                capacity_factor=capacity_factor, mesh=mesh,
                                kernel=kernel, stats=stats)
    return _moe_spec_flat(params, x, n_experts=n_experts, top_k=top_k,
                          capacity_factor=capacity_factor, kernel=kernel,
                          stats=stats)


def _dp_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def _moe_spec_ep(params: Dict, x: jax.Array, *, n_experts: int, top_k: int,
                 capacity_factor: float, mesh, kernel: bool = False,
                 stats: bool = False):
    model_n = mesh.shape["model"]
    e_loc = n_experts // model_n
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    d = x.shape[-1]

    def local_fn(router, wg, wu, wd, xl):
        n_loc = xl.shape[0]
        ax = jax.lax.axis_index("model")
        lo = ax * e_loc
        logits = jnp.einsum("nd,de->ne", xl, router)
        gates, experts = jax.lax.top_k(
            jax.nn.softmax(logits.astype(jnp.float32), axis=-1), top_k)

        # local speculative dispatch: non-resident experts are poisoned
        cap = round_capacity(n_loc, n_experts, top_k, capacity_factor)
        flat_e = experts.reshape(-1)
        is_local = (flat_e >= lo) & (flat_e < lo + e_loc)
        loc_e = jnp.where(is_local, flat_e - lo, e_loc)     # e_loc = dump row
        onehot = jax.nn.one_hot(loc_e, e_loc + 1, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        poison = (~is_local) | (pos >= cap)
        slot = jnp.where(poison, -1, loc_e * cap + pos)
        safe = jnp.maximum(slot, 0)

        src = jnp.repeat(xl, top_k, axis=0)
        if kernel:
            buf = spec_scatter_add(jnp.zeros((e_loc * cap, d), xl.dtype),
                                   slot, src)
        else:
            src = jnp.where(poison[:, None], jnp.zeros_like(src), src)
            buf = jnp.zeros((e_loc * cap, d), xl.dtype).at[safe].add(src)

        bufe = buf.reshape(e_loc, cap, d)
        g = jnp.einsum("ecd,edf->ecf", bufe, wg)
        u = jnp.einsum("ecd,edf->ecf", bufe, wu)
        h = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        h = h.reshape(e_loc * cap, d)

        gg = jnp.where(poison.reshape(-1, top_k), 0.0, gates)
        out = _combine(h, slot, gg, kernel=kernel)
        # a request commits on exactly one model shard (its expert's home)
        # unless it lost the capacity race there, so summing commits over
        # ``model`` counts each surviving request once — globally identical
        # to the flat variant's accounting.
        committed = jax.lax.psum(jnp.sum(slot >= 0), "model")
        poisoned = jax.lax.psum(n_loc * top_k - committed, dp)
        return jax.lax.psum(out, "model"), poisoned.astype(jnp.int32)

    out, poisoned = jax.shard_map(
        local_fn, mesh=mesh, check_vma=False,
        in_specs=(P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(dp, None)),
        out_specs=(P(dp, None), P()),
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"],
      x)

    if "shared_w_gate" in params:
        from .layers import swiglu
        out = out + swiglu(x, params["shared_w_gate"], params["shared_w_up"],
                           params["shared_w_down"])
    return (out, poisoned) if stats else out


def _moe_spec_tp(params: Dict, x: jax.Array, *, n_experts: int, top_k: int,
                 capacity_factor: float, mesh, kernel: bool = False,
                 stats: bool = False):
    """Fully-manual variant for expert counts below the model-axis size:
    every device holds ALL experts with a 1/model slice of the FFN width,
    dispatches its local tokens speculatively (capacity poison only), and
    psums the f-partial expert outputs once per layer."""
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    d = x.shape[-1]

    def local_fn(router, wg, wu, wd, xl):
        n_loc = xl.shape[0]
        logits = jnp.einsum("nd,de->ne", xl, router)
        gates, experts = jax.lax.top_k(
            jax.nn.softmax(logits.astype(jnp.float32), axis=-1), top_k)
        cap = round_capacity(n_loc, n_experts, top_k, capacity_factor)
        slot, gates = spec_dispatch_indices(gates, experts, cap, n_experts)
        flat = slot.reshape(-1)
        safe = jnp.maximum(flat, 0)
        src = jnp.repeat(xl, top_k, axis=0)
        if kernel:
            buf = spec_scatter_add(jnp.zeros((n_experts * cap, d), xl.dtype),
                                   flat, src)
        else:
            src = jnp.where((flat < 0)[:, None], jnp.zeros_like(src), src)
            buf = jnp.zeros((n_experts * cap, d), xl.dtype).at[safe].add(src)

        bufe = buf.reshape(n_experts, cap, d)
        g = jnp.einsum("ecd,edf->ecf", bufe, wg)     # f is the local slice
        u = jnp.einsum("ecd,edf->ecf", bufe, wu)
        h = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        h = jax.lax.psum(h, "model")                 # f-partial sums
        h = h.reshape(n_experts * cap, d)

        out = _combine(h, flat, gates, kernel=kernel)
        # every model shard dispatches the same replicated tokens, so the
        # local poison count is already the per-dp-shard total — sum over
        # the data axes only (summing over ``model`` would multiply-count).
        poisoned = jax.lax.psum(n_loc * top_k - jnp.sum(flat >= 0), dp)
        return out, poisoned.astype(jnp.int32)

    out, poisoned = jax.shard_map(
        local_fn, mesh=mesh, check_vma=False,
        in_specs=(P(None, None), P(None, None, "model"),
                  P(None, None, "model"), P(None, "model", None),
                  P(dp, None)),
        out_specs=(P(dp, None), P()),
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"],
      x)
    if "shared_w_gate" in params:
        from .layers import swiglu
        out = out + swiglu(x, params["shared_w_gate"], params["shared_w_up"],
                           params["shared_w_down"])
    return (out, poisoned) if stats else out


def _moe_spec_flat(params: Dict, x: jax.Array, *, n_experts: int,
                   top_k: int, capacity_factor: float, kernel: bool = False,
                   stats: bool = False):
    """Single-device / meshless speculative dispatch (the reference)."""
    n, d = x.shape
    router_logits = jnp.einsum("nd,de->ne", x, params["router"])
    gates, experts = jax.lax.top_k(jax.nn.softmax(
        router_logits.astype(jnp.float32), axis=-1), top_k)
    capacity = round_capacity(n, n_experts, top_k, capacity_factor)

    slot, gates = spec_dispatch_indices(gates, experts, capacity, n_experts)
    flat_slot = slot.reshape(-1)
    safe = jnp.maximum(flat_slot, 0)

    # --- speculative store into the expert buffer (poison drops) ----------
    src = jnp.repeat(x, top_k, axis=0)
    if kernel:
        # the Pallas scatter drops poisoned requests at commit itself —
        # bit-identical to the masked lax path because every non-poisoned
        # slot receives exactly one token (cumsum assignment) and both
        # paths compute 0 + row.
        buf = spec_scatter_add(jnp.zeros((n_experts * capacity, d), x.dtype),
                               flat_slot, src)
    else:
        # poisoned requests still reach the memory system but commit
        # nothing: their payload is zeroed and their (clamped) slot-0
        # write adds 0.
        src = jnp.where((flat_slot < 0)[:, None], jnp.zeros_like(src), src)
        buf = jnp.zeros((n_experts * capacity, d), x.dtype).at[safe].add(src)

    # --- expert FFN over the contiguous buffer ----------------------------
    bufe = buf.reshape(n_experts, capacity, d)
    g = jnp.einsum("ecd,edf->ecf", bufe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", bufe, params["w_up"])
    h = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w_down"])
    h = h.reshape(n_experts * capacity, d)

    # --- combine: gather back, poisoned slots contribute zero -------------
    out = _combine(h, flat_slot, gates, kernel=kernel)

    if "shared_w_gate" in params:
        from .layers import swiglu
        out = out + swiglu(x, params["shared_w_gate"], params["shared_w_up"],
                           params["shared_w_down"])
    if stats:
        return out, jnp.sum(flat_slot < 0).astype(jnp.int32)
    return out


def moe_dense(params: Dict, x: jax.Array, *, n_experts: int, top_k: int,
              stats: bool = False, **_: object):
    """If-conversion baseline: all tokens × all experts, gated (no spec)."""
    router_logits = jnp.einsum("nd,de->ne", x, params["router"])
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    mask = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], experts].set(gates)
    g = jnp.einsum("nd,edf->nef", x, params["w_gate"])
    u = jnp.einsum("nd,edf->nef", x, params["w_up"])
    h = jnp.einsum("nef,efd->ned", jax.nn.silu(g) * u, params["w_down"])
    out = jnp.einsum("ned,ne->nd", h, mask.astype(h.dtype))
    if "shared_w_gate" in params:
        from .layers import swiglu
        out = out + swiglu(x, params["shared_w_gate"], params["shared_w_up"],
                           params["shared_w_down"])
    if stats:
        # dense runs every token through every expert — nothing speculated,
        # nothing poisoned
        return out, jnp.zeros((), jnp.int32)
    return out
