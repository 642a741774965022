"""Plain reference of the ``kimi_k2_ep24`` configuration, and its weights.

Imports nothing of the program.  :func:`make_params` draws the weights
from the seed on the device, in one jitted call, in the dtype they are
served in, laid out as the serving program takes them (one stacked group
per layer: an attention sublayer, then an MoE sublayer).

:func:`final_hidden` is the model's forward pass in float32 at the
``highest`` matmul precision, straight from the semantics the
configuration file states, over one whole wave of the engine:

* rows are left-padded to the wave's longest prompt with token 0; a pad
  column is never attended as a key, and RoPE counts real tokens only;
  a pad query attends to nothing, so its softmax over the cache's
  ``max_len`` columns (all masked alike) is uniform and its output is
  the sum of the wave's prefill values over ``max_len``;
* each MoE call routes its tokens (token-major, then the top-k in
  descending gate order) over the held experts with a softmax router;
  an expert takes at most ``capacity`` dispatches per call, in that
  order, and a later dispatch is dropped; the prefill is one call over
  all ``rows x prompt`` tokens, pads included, and each decode step one
  call over the wave's rows;
* the gates are the router's probabilities, not renormalised.

Weights, norms and activations stay float32 throughout; ``lowp=True``
instead rounds every matmul operand to float8 (e4m3, one scale per
tensor): the configuration's control, one precision below bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn


def sizes(cfg: Dict) -> Dict:
    a = cfg["assumed"]
    rope = cfg["rope_scaling"]
    if rope is not None and (rope["type"], rope["factor"]) != ("yarn", 1):
        # YaRN at factor 1 leaves the frequencies and the attention scale
        # as they are: the unscaled RoPE of ``_rope``.
        raise ValueError(f"the reference computes unscaled RoPE only, not "
                         f"rope_scaling={rope!r}")
    return dict(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=a["head_dim"],
                layers=cfg["num_hidden_layers"],
                experts=cfg["n_routed_experts"],
                top_k=cfg["num_experts_per_tok"],
                ff=cfg["moe_intermediate_size"],
                shared=cfg["n_shared_experts"], vocab=cfg["vocab_size"],
                eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
                scale=cfg["routed_scaling_factor"],
                cf=a["capacity_factor"], std=a["init_std"],
                dtype=a["dtype"])


def _key(seed: int):
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def param_shapes(cfg: Dict) -> Dict:
    s = sizes(cfg)
    d, L, E, ff = s["d"], s["layers"], s["experts"], s["ff"]
    sf = ff * s["shared"]
    attn = {"ln": (L, d), "wq": (L, d, s["heads"] * s["hd"]),
            "wk": (L, d, s["kv"] * s["hd"]), "wv": (L, d, s["kv"] * s["hd"]),
            "wo": (L, s["heads"] * s["hd"], d)}
    moe = {"ln": (L, d), "router": (L, d, E), "w_gate": (L, E, d, ff),
           "w_up": (L, E, d, ff), "w_down": (L, E, ff, d)}
    if s["shared"]:
        moe.update(shared_w_gate=(L, d, sf), shared_w_up=(L, d, sf),
                   shared_w_down=(L, sf, d))
    return {"embed": (s["vocab"], d), "ln_f": (d,),
            "lm_head": (d, s["vocab"]),
            "groups": {"s0_attn": attn, "s1_moe": moe}}


def make_params(cfg: Dict, seed: int):
    """Every weight normal(0, init_std), every norm scale 1, drawn on the
    device from ``seed`` in the served dtype."""
    return params_fn(cfg)(_key(seed))


def params_fn(cfg: Dict):
    """The one jitted program that draws every weight from a key."""
    s = sizes(cfg)
    shapes = param_shapes(cfg)
    dt = jnp.dtype(s["dtype"])
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]

    @jax.jit
    def draw(key):
        out = []
        for i, (shape, path) in enumerate(zip(leaves, paths)):
            if path.endswith("['ln']") or path.endswith("['ln_f']"):
                out.append(jnp.ones(shape, dt))
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
                out.append((z * s["std"]).astype(dt))
        return jax.tree.unflatten(tree, out)

    return draw


def capacity(n_tokens: int, experts: int, top_k: int, cf: float) -> int:
    """Dispatches an expert takes per call: ``cf`` times its even share,
    plus one, rounded up to a multiple of 8 (at least 8)."""
    cap = int(cf * n_tokens * top_k / experts) + 1
    return max(8, -(-cap // 8) * 8)


# ---------------------------------------------------------------------------


def _q8(x):
    """``x`` rounded to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec: str, a, b, lowp: bool):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if lowp:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (T, H, hd); pos: (T,).  Rotates the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("s", "plen", "max_len",
                                             "lowp"))
def _attention(p, x, pad, *, s, plen, max_len, lowp):
    """One attention sublayer over the wave, a row at a time."""
    T = x.shape[1]
    rep = s["heads"] // s["kv"]
    cols = jnp.arange(T)

    def row(args):
        xr, pr = args
        h = _rms(xr, s["eps"]) * p["ln"].astype(jnp.float32)
        q = _mm("td,dk->tk", h, p["wq"], lowp).reshape(T, s["heads"],
                                                       s["hd"])
        k = _mm("td,dk->tk", h, p["wk"], lowp).reshape(T, s["kv"], s["hd"])
        v = _mm("td,dk->tk", h, p["wv"], lowp).reshape(T, s["kv"], s["hd"])
        pos = jnp.maximum(cols - pr, 0)
        q = _rope(q, pos, s["theta"])
        k = jnp.repeat(_rope(k, pos, s["theta"]), rep, axis=1)
        ve = jnp.repeat(v, rep, axis=1)
        sc = _mm("qhd,khd->hqk", q, k, lowp) / jnp.sqrt(
            jnp.float32(s["hd"]))
        ok = (cols[None, :] <= cols[:, None]) & (cols[None, :] >= pr)
        sc = jnp.where(ok[None], sc, -jnp.inf)
        real = cols >= pr
        prob = jax.nn.softmax(jnp.where(real[None, :, None], sc, 0.0), -1)
        out = _mm("hqk,khd->qhd", prob, ve, lowp)
        # a pad query: uniform over the cache's max_len columns, of which
        # the first plen hold this wave's prefill values
        pad_out = ve[:plen].sum(0) / max_len
        out = jnp.where(real[:, None, None], out, pad_out[None])
        return xr + _mm("tk,kd->td", out.reshape(T, -1), p["wo"], lowp)

    return jax.lax.map(row, (x, pad))


def _route(logits, s, n_group: int):
    """Gates (G, N, E) of the dispatches each call keeps, and how many it
    drops.  ``logits``: (G, N, E), one MoE call per leading index, tokens
    in call order."""
    g, n, e = logits.shape
    probs = jax.nn.softmax(logits, -1)
    gates, experts = jax.lax.top_k(probs, s["top_k"])          # (G, N, K)
    onehot = jax.nn.one_hot(experts.reshape(g, n * s["top_k"]), e,
                            dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, 1) * onehot).sum(-1) - 1         # (G, N*K)
    keep = pos < capacity(n_group, e, s["top_k"], s["cf"])
    kept = jnp.where(keep.reshape(g, n, s["top_k"]), gates, 0.0)
    w = (jax.nn.one_hot(experts, e, dtype=jnp.float32)
         * kept[..., None]).sum(2)
    return w * s["scale"], jnp.sum(~keep)


@functools.partial(jax.jit, static_argnames=("s", "plen", "lowp"))
def _moe(p, x, *, s, plen, lowp):
    """One MoE sublayer: the prefill as one call over every row's
    prompt columns, then one call per decode column."""
    b, T, d = x.shape
    h = _rms(x, s["eps"]) * p["ln"].astype(jnp.float32)
    logits = _mm("btd,de->bte", h, p["router"], lowp)
    w_pre, dropped = _route(logits[:, :plen].reshape(1, b * plen, -1), s,
                            b * plen)
    w_pre = w_pre.reshape(b, plen, -1)
    if T > plen:
        w_dec, d_dec = _route(logits[:, plen:].transpose(1, 0, 2), s, b)
        w = jnp.concatenate([w_pre, w_dec.transpose(1, 0, 2)], 1)
        dropped = dropped + d_dec
    else:
        w = w_pre
    flat = h.reshape(b * T, d)
    w = w.reshape(b * T, -1)

    def expert(acc, e):
        g = _mm("nd,df->nf", flat, p["w_gate"][e], lowp)
        u = _mm("nd,df->nf", flat, p["w_up"][e], lowp)
        y = _mm("nf,fd->nd", jax.nn.silu(g) * u, p["w_down"][e], lowp)
        return acc + y * w[:, e, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(flat),
                          jnp.arange(s["experts"]))
    if s["shared"]:
        g = _mm("nd,df->nf", flat, p["shared_w_gate"], lowp)
        u = _mm("nd,df->nf", flat, p["shared_w_up"], lowp)
        out = out + _mm("nf,fd->nd", jax.nn.silu(g) * u,
                        p["shared_w_down"], lowp)
    return x + out.reshape(b, T, d), dropped


def _frozen(s: Dict):
    return tuple(sorted(s.items()))


class _S(dict):
    """A hashable dict of sizes, for jit's static arguments."""

    def __hash__(self):
        return hash(_frozen(self))


def final_hidden(cfg: Dict, params, tokens: np.ndarray, pad: np.ndarray,
                 plen: int, max_len: int, rows: Sequence[int],
                 cols: Sequence[int], lowp: bool = False):
    """Final-normed hidden states (n, d) at (row, column) pairs, and the
    dispatches the MoE calls dropped over the wave.

    ``tokens``: (b, T) int32, the left-padded prompts (pad id 0) followed
    by the token each decode step was fed; ``pad``: (b,) pad lengths.
    """
    s = _S(sizes(cfg))
    g = params["groups"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    pad = jnp.asarray(pad, jnp.int32)
    dropped = 0
    for layer in range(s["layers"]):
        pa = jax.tree.map(lambda a: a[layer], g["s0_attn"])
        pm = jax.tree.map(lambda a: a[layer], g["s1_moe"])
        x = _attention(pa, x, pad, s=s, plen=plen, max_len=max_len,
                       lowp=lowp)
        x, d = _moe(pm, x, s=s, plen=plen, lowp=lowp)
        dropped += int(d)
    h = x[jnp.asarray(rows), jnp.asarray(cols)]
    return _rms(h, s["eps"]) * params["ln_f"].astype(jnp.float32), dropped


@functools.partial(jax.jit, static_argnames=("lowp",))
def _head(h, lm_head, tok, *, lowp):
    logits = _mm("nd,dv->nv", h, lm_head, lowp)
    best = logits.max(-1)
    return best, jnp.take_along_axis(logits, tok[:, None], 1)[:, 0], \
        logits.argmax(-1).astype(jnp.int32)


def head(h, lm_head, tok, lowp: bool = False, chunk: int = 1024):
    """Per position: the best logit, the logit of ``tok`` and the arg
    max, computed ``chunk`` positions at a time."""
    outs: List = []
    n = h.shape[0]
    tok = jnp.asarray(tok, jnp.int32)
    for i in range(0, n, chunk):
        j = min(n, i + chunk)
        hp = jnp.pad(h[i:j], ((0, chunk - (j - i)), (0, 0)))
        tp = jnp.pad(tok[i:j], (0, chunk - (j - i)))
        outs.append([np.asarray(o)[:j - i]
                     for o in _head(hp, lm_head, tp, lowp=lowp)])
    return [np.concatenate([o[k] for o in outs]) for k in range(3)]
