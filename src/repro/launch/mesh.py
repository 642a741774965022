"""Production mesh + sharding rules.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state): single-pod ``(16, 16)`` over ``("data", "model")``, multi-pod
``(2, 16, 16)`` over ``("pod", "data", "model")`` — 256-chip v5e pods, 512
chips across two pods.

Sharding policy (DESIGN.md §5):

* batch over ``(pod, data)`` (pure DP across pods by default — cross-pod
  traffic is one grad all-reduce; the pipelined alternative is the §Perf
  hillclimb);
* TP over ``model``: attention heads / FFN width / vocab;
* EP folded into ``model``: experts shard over it when ``E % model == 0``
  (kimi: 384/16), else the expert FFN dim shards (grok: 8 experts × 2048);
* FSDP (ZeRO-3): parameters & optimizer state additionally shard their
  largest replicated dim over ``data`` for configs above ``fsdp_threshold``.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, param_count


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------


def param_spec(path: str, shape: Tuple[int, ...], cfg: ArchConfig,
               mesh: Mesh, fsdp: bool) -> P:
    """PartitionSpec for one parameter, keyed on its pytree path."""
    model_n = mesh.shape["model"]
    fs = "data" if fsdp else None

    def ok(dim: int, size: Optional[int]) -> bool:
        return size is not None and dim % _axis(mesh, size) == 0 if False \
            else True

    if len(shape) <= 1 or "ln" in path:      # norms, biases, vectors
        return P(*([None] * len(shape)))

    # --- embeddings / head: vocab on model, d on data(FSDP) ---------------
    if ("embed" in path or "lm_head" in path) and len(shape) == 2:
        v_dim = 0 if "embed" in path else 1
        spec = [None] * len(shape)
        if shape[v_dim] % model_n == 0:
            spec[v_dim] = "model"
        if fsdp and shape[1 - v_dim] % mesh.shape["data"] == 0:
            spec[1 - v_dim] = fs
        return P(*spec)

    # --- MoE experts -------------------------------------------------------
    if re.search(r"(w_gate|w_up|w_down)$", path) and len(shape) == 3:
        e, a, b = shape
        if e % model_n == 0:                       # EP on the model axis
            spec = ["model", None, None]
            if fsdp and a % mesh.shape["data"] == 0:
                spec[1] = fs
            return P(*spec)
        # few experts: shard the FFN dim (TP inside each expert)
        ff_dim = 2 if "w_down" not in path else 1
        spec = [None, None, None]
        if shape[ff_dim] % model_n == 0:
            spec[ff_dim] = "model"
        other = 1 if ff_dim == 2 else 2
        if fsdp and shape[other] % mesh.shape["data"] == 0:
            spec[other] = fs
        return P(*spec)

    if "router" in path:
        return P(None, None)

    # --- attention / dense MLP / SSM projections (2-D) ---------------------
    if len(shape) == 2:
        d_in, d_out = shape
        # column-parallel by default (wq/wk/wv/w_gate/w_up/in_proj...)
        # row-parallel for the contraction-side mats (wo / w_down / out_proj)
        row_parallel = bool(re.search(r"(wo|w_down|out_proj)$", path))
        tp_dim = 0 if row_parallel else 1
        spec = [None, None]
        if shape[tp_dim] % model_n == 0:
            spec[tp_dim] = "model"
        if fsdp and shape[1 - tp_dim] % mesh.shape["data"] == 0 \
                and spec[1 - tp_dim] is None:
            spec[1 - tp_dim] = fs
        return P(*spec)

    return P(*([None] * len(shape)))


def _axis(mesh: Mesh, size):
    return size


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def shard_pytree_specs(tree_shapes: Any, cfg: ArchConfig, mesh: Mesh,
                       fsdp: bool) -> Any:
    """Map a pytree of ShapeDtypeStructs to NamedShardings."""
    def one(path, leaf):
        spec = param_spec(_path_str(path), leaf.shape, cfg, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, tree_shapes)


def needs_fsdp(cfg: ArchConfig) -> bool:
    total, _ = param_count(cfg)
    return total * 2 > 8e9      # >8 GB of bf16 params per TP shard group


def batch_spec(mesh: Mesh, *, shard_batch: bool = True,
               seq_axis: bool = False) -> P:
    """Token batches: batch dim over (pod, data); long-context single-batch
    cells shard the sequence dim instead (SP)."""
    if seq_axis:
        return P(None, data_axes(mesh))
    return P(data_axes(mesh), None)
