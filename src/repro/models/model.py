"""Model builder: one code path for all 10 assigned architectures.

A config compiles to a **repeating layer group** (DESIGN.md §4):

=========  ====================================================handy========
family     group pattern (scanned with ``lax.scan`` + remat)
=========  ============================================================
dense      [attn, mlp]                        × n_layers
moe        [attn, moe]                        × n_layers
ssm        [rwkv6, mlp]                       × n_layers
hybrid     [(mamba, mlp/moe)×7, (attn, moe)]  × n_layers/8   (jamba 1:7)
vlm        [(attn, mlp)×4, (cross, mlp)]      × n_layers/5
encdec     encoder [attn, mlp]×E  +  decoder [self, cross, mlp]×L
=========  ============================================================

Scanning over stacked group params keeps the HLO size (and compile time)
independent of depth — essential for the 512-device dry-run.  KV caches and
SSM states are stacked over groups and carried through the same scan.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers as L
from . import moe as moe_mod
from . import ssm as ssm_mod


class Model(NamedTuple):
    cfg: ArchConfig
    # "spec" (paper technique, lax reference) | "spec-kernel" (same dispatch
    # through the Pallas spec_scatter_add/spec_gather kernels) | "dense"
    # (STA baseline)
    dispatch: str

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> Dict:
        """Random parameters, drawn as one jitted program: op by op, every
        weight's float32 draw would be live at once before its cast (about
        3.8 GB per expert weight at Kimi's full widths)."""
        return jax.jit(self._init)(key)

    def _init(self, key: jax.Array) -> Dict:
        cfg = self.cfg
        d, v = cfg.d_model, cfg.vocab
        dt = cfg.jdtype
        k_emb, k_layers, k_enc, k_head = jax.random.split(key, 4)

        def norm(shape):
            return jnp.ones(shape, dt)

        def dense(key, shape, scale=0.02):
            # the barrier stops XLA folding ``scale`` into the sampler's own
            # constants, so the draws match op-by-op execution bit for bit
            z = jax.lax.optimization_barrier(
                jax.random.normal(key, shape, jnp.float32))
            return (z * scale).astype(dt)

        def sublayer_params(key, kind):
            ks = jax.random.split(key, 12)
            hd = cfg.hd
            if kind in ("attn", "cross"):
                return {
                    "ln": norm((d,)),
                    "wq": dense(ks[0], (d, cfg.n_heads * hd)),
                    "wk": dense(ks[1], (d, cfg.n_kv_heads * hd)),
                    "wv": dense(ks[2], (d, cfg.n_kv_heads * hd)),
                    "wo": dense(ks[3], (cfg.n_heads * hd, d)),
                }
            if kind == "mlp":
                return {
                    "ln": norm((d,)),
                    "w_gate": dense(ks[0], (d, cfg.d_ff)),
                    "w_up": dense(ks[1], (d, cfg.d_ff)),
                    "w_down": dense(ks[2], (cfg.d_ff, d)),
                }
            if kind == "moe":
                ff = cfg.moe_d_ff or cfg.d_ff
                p = {
                    "ln": norm((d,)),
                    "router": dense(ks[0], (d, cfg.n_experts)),
                    "w_gate": dense(ks[1], (cfg.n_experts, d, ff)),
                    "w_up": dense(ks[2], (cfg.n_experts, d, ff)),
                    "w_down": dense(ks[3], (cfg.n_experts, ff, d)),
                }
                if cfg.n_shared_experts:
                    sf = ff * cfg.n_shared_experts
                    p.update(shared_w_gate=dense(ks[4], (d, sf)),
                             shared_w_up=dense(ks[5], (d, sf)),
                             shared_w_down=dense(ks[6], (sf, d)))
                return p
            if kind == "rwkv":
                return {
                    "ln": norm((d,)),
                    "mu": jnp.full((4, d), 0.5, dt),
                    "wr": dense(ks[0], (d, d)),
                    "wk": dense(ks[1], (d, d)),
                    "wv": dense(ks[2], (d, d)),
                    "ww": dense(ks[3], (d, d), 0.01),
                    "w_bias": jnp.full((d,), 2.0, dt),
                    "u": dense(ks[4], (d,)),
                    "wo": dense(ks[5], (d, d)),
                }
            if kind == "mamba":
                n = cfg.ssm_d_state
                return {
                    "ln": norm((d,)),
                    "in_proj": dense(ks[0], (d, d)),
                    "gate_proj": dense(ks[1], (d, d)),
                    "dt_proj": dense(ks[2], (d,)),
                    "b_proj": dense(ks[3], (d, n)),
                    "c_proj": dense(ks[4], (d, n)),
                    "a_log": jnp.zeros((d, n), jnp.float32),
                    "out_proj": dense(ks[5], (d, d)),
                }
            raise ValueError(kind)

        pattern = group_pattern(cfg)
        n_groups = group_count(cfg)

        def group_init(key):
            ks = jax.random.split(key, len(pattern))
            return {f"s{j}_{kind}": sublayer_params(ks[j], kind)
                    for j, (kind) in enumerate(pattern)}

        params = {
            "embed": dense(k_emb, (v, d)),
            "ln_f": norm((d,)),
            "lm_head": dense(k_head, (d, v)),
            "groups": jax.vmap(group_init)(
                jax.random.split(k_layers, n_groups)),
        }
        if cfg.n_enc_layers:
            def enc_init(key):
                ks = jax.random.split(key, 2)
                return {"s0_attn": sublayer_params(ks[0], "attn"),
                        "s1_mlp": sublayer_params(ks[1], "mlp")}
            params["enc_groups"] = jax.vmap(enc_init)(
                jax.random.split(k_enc, cfg.n_enc_layers))
            params["enc_ln_f"] = norm((d,))
        return params

    # -------------------------------------------------------------- forward
    def _sublayer(self, kind: str, p: Dict, x: jax.Array, *,
                  pos_offset=0, cross_kv=None, causal=True,
                  kv_cache=None, cache_len=None, state=None,
                  pad_lens=None, moe_stats=False):
        cfg = self.cfg
        h = L.rms_norm(x, p["ln"])
        new_cache = new_state = None
        poison = jnp.zeros((), jnp.int32) if moe_stats else None
        if kind == "cross":
            # project the (stubbed) memory with this sublayer's K/V weights;
            # recomputed per step in decode (static memory — a known future
            # optimization is caching these, see EXPERIMENTS.md §Perf)
            mem = cross_kv  # (B, S, d)
            kk = jnp.einsum("bsd,dhk->bhsk", mem,
                            p["wk"].reshape(cfg.d_model, cfg.n_kv_heads,
                                            cfg.hd))
            vv = jnp.einsum("bsd,dhk->bhsk", mem,
                            p["wv"].reshape(cfg.d_model, cfg.n_kv_heads,
                                            cfg.hd))
            out, _ = L.gqa_attention(
                p, h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, theta=cfg.rope_theta,
                cross_kv=(kk.astype(h.dtype), vv.astype(h.dtype)))
        elif kind == "attn":
            out, new_cache = L.gqa_attention(
                p, h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, theta=cfg.rope_theta,
                pos_offset=pos_offset, causal=causal,
                kv_cache=kv_cache, cache_len=cache_len, pad_len=pad_lens)
        elif kind == "mlp":
            out = L.swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        elif kind == "moe":
            b, t, d = h.shape
            if self.dispatch == "dense":
                res = moe_mod.moe_dense(
                    p, h.reshape(b * t, d), n_experts=cfg.n_experts,
                    top_k=cfg.top_k, stats=moe_stats)
            else:
                res = moe_mod.moe_spec(
                    p, h.reshape(b * t, d), n_experts=cfg.n_experts,
                    top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                    kernel=self.dispatch == "spec-kernel", stats=moe_stats)
            if moe_stats:
                out, poison = res
            else:
                out = res
            out = out.reshape(b, t, d)
        elif kind == "rwkv":
            res = ssm_mod.rwkv6_block(p, h, n_heads=cfg.d_model // cfg.hd,
                                      head_dim=cfg.hd, state=state,
                                      return_state=state is not None)
            out, new_state = res if state is not None else (res, None)
        elif kind == "mamba":
            res = ssm_mod.mamba_block(p, h, d_state=cfg.ssm_d_state,
                                      state=state,
                                      return_state=state is not None)
            out, new_state = res if state is not None else (res, None)
        else:
            raise ValueError(kind)
        return x + out, new_cache, new_state, poison

    def _run_groups(self, params: Dict, x: jax.Array, *, pos_offset=0,
                    cross_kv=None, caches=None, cache_len=None,
                    states=None, pad_lens=None, collect_stats=False):
        """Scan the stacked layer groups.  caches/states: stacked pytrees
        (leading dim = n_groups) or None (training, no cache).

        ``pad_lens`` ((B,) int32, left-pad length per row) flows to every
        attention sublayer so padded prompt slots are poisoned rather than
        attended.  ``collect_stats=True`` appends a summed MoE poison count
        to the return tuple.
        """
        cfg = self.cfg
        pattern = group_pattern(cfg)

        def group_fn(h, gp, gcache, gstate):
            new_caches, new_states = [], []
            gpoison = jnp.zeros((), jnp.int32) if collect_stats else None
            for j, kind in enumerate(pattern):
                p = gp[f"s{j}_{kind}"]
                kv = gcache[len(new_caches)] if (
                    gcache is not None and kind == "attn") else None
                st = gstate[len(new_states)] if (
                    gstate is not None and kind in ("rwkv", "mamba")) else None
                h, nkv, nst, poison = self._sublayer(
                    kind, p, h, pos_offset=pos_offset, cross_kv=cross_kv,
                    kv_cache=kv, cache_len=cache_len, state=st,
                    pad_lens=pad_lens,
                    moe_stats=collect_stats and kind == "moe")
                if kind == "attn" and gcache is not None:
                    new_caches.append(nkv)
                if kind in ("rwkv", "mamba") and gstate is not None:
                    new_states.append(nst)
                if collect_stats and kind == "moe":
                    gpoison = gpoison + poison
            return h, tuple(new_caches), tuple(new_states), gpoison

        if caches is None and states is None:
            # training: remat each group; scan keeps HLO depth-independent
            train_fn = jax.checkpoint(
                lambda h, gp: (group_fn(h, gp, None, None)[0], None),
                policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = jax.lax.scan(train_fn, x, params["groups"])
            return x, None, None

        def serve_fn(h, inp):
            gp, gcache, gstate = inp
            h, ncaches, nstates, gpoison = group_fn(h, gp, gcache, gstate)
            ys = (ncaches or None, nstates or None)
            if collect_stats:
                ys = ys + (gpoison,)
            return h, ys

        if collect_stats:
            x, (new_caches, new_states, poison) = jax.lax.scan(
                serve_fn, x, (params["groups"], caches, states))
            return x, new_caches, new_states, poison.sum()
        x, (new_caches, new_states) = jax.lax.scan(
            serve_fn, x, (params["groups"], caches, states))
        return x, new_caches, new_states

    def _encode(self, params: Dict, frames: jax.Array) -> jax.Array:
        """Whisper encoder over stubbed frame embeddings (bidirectional)."""
        def enc_fn(h, gp):
            hh = L.rms_norm(h, gp["s0_attn"]["ln"])
            out, _ = L.gqa_attention(
                gp["s0_attn"], hh, n_heads=self.cfg.n_heads,
                n_kv_heads=self.cfg.n_kv_heads, head_dim=self.cfg.hd,
                theta=self.cfg.rope_theta, causal=False)
            h = h + out
            hh = L.rms_norm(h, gp["s1_mlp"]["ln"])
            h = h + L.swiglu(hh, gp["s1_mlp"]["w_gate"],
                             gp["s1_mlp"]["w_up"], gp["s1_mlp"]["w_down"])
            return h, None

        enc_fn = jax.checkpoint(enc_fn,
                                policy=jax.checkpoint_policies.nothing_saveable)
        h, _ = jax.lax.scan(enc_fn, frames, params["enc_groups"])
        return L.rms_norm(h, params["enc_ln_f"])

    def _cross_kv(self, params: Dict, memory: jax.Array):
        """Pre-compute cross-attention K/V from encoder/patch memory.  The
        cross K/V projections live in each cross sublayer; to stay scannable
        we compute them inside the sublayer instead (memory passed through),
        so here we just return the memory tensor."""
        return memory

    # ----------------------------------------------------------------- train
    def loss(self, params: Dict, batch: Dict) -> jax.Array:
        cfg = self.cfg
        tokens = batch["tokens"]              # (B, T)
        x = jnp.take(params["embed"], tokens, axis=0)
        cross = None
        if cfg.family == "encdec":
            cross = self._make_cross(params, self._encode(
                params, batch["frames"]))
        elif cfg.family == "vlm":
            cross = self._make_cross(params, batch["patches"])
        x, _, _ = self._run_groups(params, x, cross_kv=cross)
        x = L.rms_norm(x, params["ln_f"])
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
        logits = logits[:, :-1].astype(jnp.float32)
        labels = tokens[:, 1:]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)
        return nll.mean()

    def _make_cross(self, params: Dict, memory: jax.Array):
        """Cross-attn K/V are computed per-sublayer from this memory; we
        project lazily inside gqa_attention via wk/wv on the memory."""
        return memory

    # ----------------------------------------------------------------- serve
    def init_cache(self, batch: int, max_len: int) -> Tuple:
        cfg = self.cfg
        pattern = group_pattern(cfg)
        n_groups = group_count(cfg)
        dt = cfg.jdtype
        caches, states = [], []
        for kind in pattern:
            if kind == "attn":   # cross K/V recompute from static memory
                shape = (n_groups, batch, cfg.n_kv_heads, max_len, cfg.hd)
                caches.append((jnp.zeros(shape, dt), jnp.zeros(shape, dt)))
            elif kind == "rwkv":
                h = cfg.d_model // cfg.hd
                states.append((
                    jnp.zeros((n_groups, batch, h, cfg.hd, cfg.hd),
                              jnp.float32),
                    jnp.zeros((n_groups, batch, cfg.d_model),
                              jnp.float32)))   # token-shift carry
            elif kind == "mamba":
                states.append(jnp.zeros(
                    (n_groups, batch, cfg.d_model, cfg.ssm_d_state),
                    jnp.float32))
        return (tuple(caches) or None, tuple(states) or None)

    def decode_step(self, params: Dict, cache, tokens: jax.Array,
                    cache_len, memory: Optional[jax.Array] = None, *,
                    pad_lens=None, return_stats: bool = False):
        """One-token step: tokens (B, 1); cache from init_cache/prefill.

        ``pad_lens`` ((B,) int32): per-row left-pad length — padded cache
        slots are masked out of attention and RoPE positions count real
        tokens only, so batched decode matches each request's solo run.
        ``return_stats=True`` appends ``{"moe_poison": ...}`` (summed
        poisoned MoE dispatch requests this step) to the return tuple.
        """
        caches, states = cache
        x = jnp.take(params["embed"], tokens, axis=0)
        cross = memory
        res = self._run_groups(
            params, x, pos_offset=cache_len, cross_kv=cross,
            caches=caches, cache_len=cache_len, states=states,
            pad_lens=pad_lens, collect_stats=return_stats)
        x, ncaches, nstates = res[:3]
        x = L.rms_norm(x, params["ln_f"])
        logits = jnp.einsum("btd,dv->btv", x, params["lm_head"])
        if return_stats:
            return logits[:, -1], (ncaches, nstates), {"moe_poison": res[3]}
        return logits[:, -1], (ncaches, nstates)

    def prefill(self, params: Dict, tokens: jax.Array, max_len: int,
                memory: Optional[jax.Array] = None, *,
                pad_lens=None, return_stats: bool = False):
        """Prefill a fresh cache with a full prompt; returns last logits.

        See :meth:`decode_step` for ``pad_lens`` / ``return_stats``.
        """
        b, t = tokens.shape
        cache = self.init_cache(b, max_len)
        caches, states = cache
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.cfg.family == "encdec" and memory is not None:
            memory = self._encode(params, memory)
        res = self._run_groups(
            params, x, pos_offset=0, cross_kv=memory,
            caches=caches, cache_len=0, states=states,
            pad_lens=pad_lens, collect_stats=return_stats)
        x, ncaches, nstates = res[:3]
        x = L.rms_norm(x, params["ln_f"])
        logits = jnp.einsum("btd,dv->btv", x[:, -1:], params["lm_head"])
        if return_stats:
            return logits[:, -1], (ncaches, nstates), {"moe_poison": res[3]}
        return logits[:, -1], (ncaches, nstates)


# ---------------------------------------------------------------------------
# layer-group schedules
# ---------------------------------------------------------------------------


def group_pattern(cfg: ArchConfig) -> Tuple[str, ...]:
    if cfg.family == "dense":
        return ("attn", "mlp")
    if cfg.family == "moe":
        return ("attn", "moe")
    if cfg.family == "ssm":
        return ("rwkv", "mlp")
    if cfg.family == "hybrid":
        out = []
        stride = cfg.attn_stride
        for j in range(stride):
            out.append("attn" if j == stride - 1 else "mamba")
            out.append("moe" if (j % cfg.moe_every) == cfg.moe_every - 1
                       else "mlp")
        return tuple(out)
    if cfg.family == "vlm":
        out = []
        for j in range(cfg.cross_stride):
            out.append("cross" if j == cfg.cross_stride - 1 else "attn")
            out.append("mlp")
        return tuple(out)
    if cfg.family == "encdec":
        return ("attn", "cross", "mlp")   # decoder group
    raise ValueError(cfg.family)


def group_count(cfg: ArchConfig) -> int:
    if cfg.family == "hybrid":
        assert cfg.n_layers % cfg.attn_stride == 0
        return cfg.n_layers // cfg.attn_stride
    if cfg.family == "vlm":
        assert cfg.n_layers % cfg.cross_stride == 0
        return cfg.n_layers // cfg.cross_stride
    return cfg.n_layers


def build_model(cfg: ArchConfig, dispatch: str = "spec") -> Model:
    return Model(cfg, dispatch)
