import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell with
ShapeDtypeStruct inputs (no allocation) and dump memory/cost/collective
numbers for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

MUST be run as its own process (the XLA_FLAGS line above is read once at
first jax init) — ``dryrun_all.py`` drives one subprocess per cell.
"""

import argparse
import json
import re
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, get, param_count
from repro.launch import mesh as mesh_mod
from repro.launch.hlo_cost import analyze_hlo
from repro.models.model import build_model
from repro.train.train_step import make_train_step

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


def shape_skip_reason(cfg: ArchConfig, shape: str) -> Optional[str]:
    if shape == "long_500k" and not cfg.sub_quadratic:
        return ("pure full-attention arch: 524k-token cache at batch=1 is "
                "out of scope per the shape table (DESIGN.md §6)")
    return None


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, weak-type-correct, shardable)
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, shape: str) -> Dict[str, jax.ShapeDtypeStruct]:
    info = SHAPES[shape]
    b = info["batch"]
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if info["kind"] == "train":
        out["tokens"] = jax.ShapeDtypeStruct((b, info["seq"]), jnp.int32)
    elif info["kind"] == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((b, info["seq"]), jnp.int32)
    else:  # decode: one new token against a seq-long cache
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    if cfg.family == "encdec":
        out["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.enc_len, cfg.d_model), cfg.jdtype)
    if cfg.family == "vlm":
        out["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.n_patches, cfg.d_model), cfg.jdtype)
    return out


def cache_specs(cfg: ArchConfig, batch: int, seq: int, mesh,
                seq_sharded: bool) -> Tuple[Any, Any]:
    """(ShapeDtypeStructs, NamedShardings) for the decode cache pytree."""
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init_cache(batch, seq))
    dp = mesh_mod.data_axes(mesh)

    def spec_for(leaf: jax.ShapeDtypeStruct) -> P:
        shp = leaf.shape
        if len(shp) == 5 and shp[2] == cfg.n_kv_heads:   # KV (G,B,H,T,hd)
            # sequence-parallel cache: T shards on the model axis (the
            # 1-token decode psum over scores is tiny); batch=1 long-context
            # cells additionally spread T over the data axis
            b_ax = dp if (not seq_sharded and shp[1] % _dp(mesh) == 0) else None
            t_axes = (tuple(dp) + ("model",)) if seq_sharded else ("model",)
            n_t = 1
            for a in t_axes:
                n_t *= mesh.shape[a]
            t_ax = t_axes if shp[3] % n_t == 0 else None
            return P(None, b_ax, None, t_ax, None)
        if len(shp) == 5:                                 # rwkv (G,B,H,k,v)
            b_ax = dp if shp[1] % _dp(mesh) == 0 else None
            h_ax = "model" if shp[2] % mesh.shape["model"] == 0 else None
            return P(None, b_ax, h_ax, None, None)
        if len(shp) == 4:                                 # mamba (G,B,D,N)
            b_ax = dp if shp[1] % _dp(mesh) == 0 else None
            d_ax = "model" if shp[2] % mesh.shape["model"] == 0 else None
            return P(None, b_ax, d_ax, None)
        if len(shp) == 3:                                 # rwkv shift (G,B,D)
            b_ax = dp if shp[1] % _dp(mesh) == 0 else None
            d_ax = "model" if shp[2] % mesh.shape["model"] == 0 else None
            return P(None, b_ax, d_ax)
        return P(*([None] * len(shp)))

    shardings = jax.tree.map(
        lambda l: NamedSharding(mesh, spec_for(l)), shapes)
    return shapes, shardings


def _dp(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


# ---------------------------------------------------------------------------
# lower + compile one cell
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape: str, multi_pod: bool,
             dispatch: str = "spec", extra_tags: str = "") -> Dict:
    cfg = get(arch)
    reason = shape_skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape,
                "mesh": "multi" if multi_pod else "single",
                "skipped": reason}

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    info = SHAPES[shape]
    model = build_model(cfg, dispatch=dispatch)
    fsdp = mesh_mod.needs_fsdp(cfg)
    dp = mesh_mod.data_axes(mesh)

    ins = input_specs(cfg, shape)
    in_shardings_batch = {
        k: NamedSharding(mesh, P(dp, *([None] * (len(v.shape) - 1))))
        for k, v in ins.items()
    }
    if info["batch"] % _dp(mesh) != 0:   # batch=1 long-context: replicate
        in_shardings_batch = {
            k: NamedSharding(mesh, P(*([None] * len(v.shape))))
            for k, v in ins.items()}

    with jax.set_mesh(mesh):
        if info["kind"] == "train":
            init_state, train_step, opt_name = make_train_step(model)
            state_shapes = jax.eval_shape(
                init_state, jax.ShapeDtypeStruct((2,), jnp.uint32))
            state_sh = mesh_mod.shard_pytree_specs(state_shapes, cfg, mesh,
                                                   fsdp)
            fn = jax.jit(train_step,
                         in_shardings=(state_sh, in_shardings_batch),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
            lowered = fn.lower(state_shapes, ins)
        elif info["kind"] == "prefill":
            cshapes, csh = cache_specs(cfg, info["batch"], info["seq"], mesh,
                                       seq_sharded=False)
            pshapes = jax.eval_shape(model.init,
                                     jax.ShapeDtypeStruct((2,), jnp.uint32))
            psh = mesh_mod.shard_pytree_specs(pshapes, cfg, mesh, fsdp=False)
            v_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
            logits_sh = NamedSharding(mesh, P(dp, v_ax))
            mem_spec = None
            if cfg.family == "encdec":
                mem_spec = ins.pop("frames")
            if cfg.family == "vlm":
                mem_spec = ins.pop("patches")
            in_shardings_batch = {
                k: NamedSharding(mesh, P(dp, None))
                for k in ("tokens",)}

            def prefill_fn(params, tokens, memory=None):
                return model.prefill(params, tokens, max_len=info["seq"],
                                     memory=memory)

            args = [pshapes, ins["tokens"]]
            in_sh = [psh, in_shardings_batch["tokens"]]
            if mem_spec is not None:
                args.append(mem_spec)
                in_sh.append(NamedSharding(mesh, P(dp, None, None)))
            fn = jax.jit(prefill_fn, in_shardings=tuple(in_sh),
                         out_shardings=(logits_sh, csh))
            lowered = fn.lower(*args)
        else:  # decode
            seq_sharded = info["batch"] % _dp(mesh) != 0
            cshapes, csh = cache_specs(cfg, info["batch"], info["seq"], mesh,
                                       seq_sharded=seq_sharded)
            pshapes = jax.eval_shape(model.init,
                                     jax.ShapeDtypeStruct((2,), jnp.uint32))
            psh = mesh_mod.shard_pytree_specs(pshapes, cfg, mesh, fsdp=False)
            tok_sh = in_shardings_batch["tokens"]
            v_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
            logits_spec = (P(dp, v_ax) if info["batch"] % _dp(mesh) == 0
                           else P(None, v_ax))
            mem_args, mem_sh = [], []
            if cfg.family in ("encdec", "vlm"):
                key = "frames" if cfg.family == "encdec" else "patches"
                ms = input_specs(cfg, shape)[key]
                mem_args.append(ms)
                mem_sh.append(NamedSharding(
                    mesh, P(dp if ms.shape[0] % _dp(mesh) == 0 else None,
                            None, None)))

            def decode_fn(params, cache, tokens, *memory):
                mem = memory[0] if memory else None
                if cfg.family == "encdec":
                    mem = model._encode(params, mem)
                return model.decode_step(params, cache, tokens,
                                         info["seq"] - 1, memory=mem)

            fn = jax.jit(
                decode_fn,
                in_shardings=(psh, csh, tok_sh, *mem_sh),
                out_shardings=(NamedSharding(mesh, logits_spec), csh),
                donate_argnums=(1,))
            lowered = fn.lower(pshapes, cshapes, ins["tokens"], *mem_args)

        compiled = lowered.compile()

    # ---- harvest ----------------------------------------------------------
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = analyze_hlo(compiled.as_text())
    total, active = param_count(cfg)
    out = {
        "arch": arch, "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": mesh.devices.size,
        "dispatch": dispatch,
        "tags": extra_tags,
        "params_total": total,
        "params_active": active,
        # xla cost_analysis (while bodies counted ONCE — kept for reference)
        "xla_flops": float(cost.get("flops", -1)) if cost else -1,
        "xla_bytes": float(cost.get("bytes accessed", -1)) if cost else -1,
        # trip-count-aware HLO parse (per-device): the roofline source
        "flops": hlo["dot_flops"],
        "bytes_accessed": hlo["dot_bytes"],
        "collective_bytes": {
            k: hlo.get(k, 0.0)
            for k in ("all-gather", "all-reduce", "reduce-scatter",
                      "all-to-all", "collective-permute")} |
            {"total": hlo["collective_total"]},
        "memory_analysis": _mem_dict(mem),
    }
    print(json.dumps({k: v for k, v in out.items()
                      if k != "memory_analysis"}, indent=None))
    print("memory_analysis:", out["memory_analysis"])
    return out


def _mem_dict(mem) -> Dict:
    if mem is None:
        return {}
    out = {}
    for f in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            out[f] = int(v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dispatch", default="spec",
                    choices=("spec", "dense"))
    ap.add_argument("--tags", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    res = run_cell(args.arch, args.shape, args.multi_pod,
                   dispatch=args.dispatch, extra_tags=args.tags)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(res, fh, indent=2)
    return 0 if ("skipped" in res or res.get("flops", -1) != 0) else 1


if __name__ == "__main__":
    sys.exit(main())
