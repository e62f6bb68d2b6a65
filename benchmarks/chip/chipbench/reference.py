"""Plain reference of what the timed path computes, from the config file.

The decoder (RMSNorm, GQA attention with RoPE, optional q/k norm and
q/k/v bias, SwiGLU, tied or separate head), its next-token loss, AdamW
with global-norm clipping, and the consensus rounds of the ring
(neighbour mean, proximal pull, dual, residuals, objective probes, the
penalty update of scheme ``nap``).
Written in plain ``jax.numpy`` at float32 with ``HIGHEST`` matmul
precision; parameters are stored in the configuration's dtype, as the
configuration states. Imports nothing of the program.

``precision="fp8"`` is the control: every matmul operand rounded to
float8 e4m3 with a per-tensor absmax scale, the next precision below the
configuration's bfloat16. It must fail the comparison.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


class Arch(NamedTuple):
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    tied: bool
    qkv_bias: bool
    qk_norm: bool
    eps: float
    theta: float
    dtype: str


def arch(cfg: dict) -> Arch:
    return Arch(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                tied=bool(cfg["tie_word_embeddings"]),
                qkv_bias=bool(cfg.get("attention_bias", False)),
                qk_norm=cfg["model_type"] == "qwen3",
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                dtype=cfg["torch_dtype"])


def param_shapes(a: Arch) -> dict:
    """The parameter tree the launcher's model holds, as shapes."""
    dt = jnp.dtype(a.dtype)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dt)   # noqa: E731
    L = a.layers
    attn = {"wq": s(L, a.d, a.heads, a.hd), "wk": s(L, a.d, a.kv, a.hd),
            "wv": s(L, a.d, a.kv, a.hd), "wo": s(L, a.heads, a.hd, a.d)}
    if a.qkv_bias:
        attn.update(bq=s(L, a.heads, a.hd), bk=s(L, a.kv, a.hd),
                    bv=s(L, a.kv, a.hd))
    if a.qk_norm:
        attn.update(qn=s(L, a.hd), kn=s(L, a.hd))
    out = {"embed": s(a.vocab, a.d),
           "blocks": {"ln1": s(L, a.d), "ln2": s(L, a.d), "attn": attn,
                      "mlp": {"wi_gate": s(L, a.d, a.ff),
                              "wi_up": s(L, a.d, a.ff),
                              "wo": s(L, a.ff, a.d)}},
           "final_norm": s(a.d)}
    if not a.tied:
        out["lm_head"] = s(a.d, a.vocab)
    return out


def param_count(a: Arch) -> int:
    return sum(math.prod(x.shape)
               for x in jax.tree_util.tree_leaves(param_shapes(a)))


# ------------------------------------------------------------- forward ----
def _fp8(x):
    """Per-tensor absmax e4m3 rounding, gradient passed straight through."""
    amax = jnp.maximum(jnp.max(jnp.abs(jax.lax.stop_gradient(x))), 1e-30)
    scale = E4M3_MAX / amax
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, x, w, fp8):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _block(a: Arch, fp8: bool, x, p, cos, sin):
    b, n, _ = x.shape
    h = _rms(x, p["ln1"], a.eps)
    at = p["attn"]
    q = _mm("bsd,dhk->bshk", h, at["wq"], fp8)
    k = _mm("bsd,dhk->bshk", h, at["wk"], fp8)
    v = _mm("bsd,dhk->bshk", h, at["wv"], fp8)
    if a.qkv_bias:
        q, k, v = q + at["bq"], k + at["bk"], v + at["bv"]
    if a.qk_norm:
        q, k = _rms(q, at["qn"], a.eps), _rms(k, at["kn"], a.eps)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    rep = a.heads // a.kv                     # query head h reads kv h//rep
    q = q.reshape(b, n, a.kv, rep, a.hd)
    logits = _mm("bqgrd,bkgd->bgrqk", q, k, fp8) / math.sqrt(a.hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    logits = jnp.where(causal, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    o = _mm("bgrqk,bkgd->bqgrd", probs, v, fp8).reshape(b, n, a.heads, a.hd)
    x = x + _mm("bshk,hkd->bsd", o, at["wo"], fp8)
    h2 = _rms(x, p["ln2"], a.eps)
    ml = p["mlp"]
    g = _mm("bsd,df->bsf", h2, ml["wi_gate"], fp8)
    u = _mm("bsd,df->bsf", h2, ml["wi_up"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, ml["wo"], fp8)


def loss(a: Arch, fp8: bool, params: dict, tokens, labels):
    """Mean next-token cross-entropy over the positions with a label."""
    p = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), params)
    n = tokens.shape[-1]
    half = a.hd // 2
    freqs = 1.0 / (a.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = p["embed"][tokens]
    block = jax.checkpoint(functools.partial(_block, a, fp8))
    for layer in range(a.layers):
        x = block(x, jax.tree_util.tree_map(lambda t: t[layer], p["blocks"]),
                  cos, sin)
    x = _rms(x, p["final_norm"], a.eps)
    head = p["embed"].T if a.tied else p["lm_head"]
    logits = _mm("bsd,dv->bsv", x, head, fp8)
    lse = jax.nn.logsumexp(logits, axis=-1)
    safe = jnp.maximum(labels, 0)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return ((lse - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ----------------------------------------------------------- optimizer ----
def adamw(opt: dict, t, params, grads, m, v):
    """One AdamW step (global-norm clip), parameters kept in their dtype.

    Returns (params, m, v, the clipped gradient the moments took)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(
        grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    step = t + 1.0
    bc1 = 1.0 - opt["b1"] ** step
    bc2 = 1.0 - opt["b2"] ** step

    def one(p, g, m, v):
        g = g * clip
        m = opt["b1"] * m + (1 - opt["b1"]) * g
        v = opt["b2"] * v + (1 - opt["b2"]) * g * g
        p32 = p.astype(jnp.float32)
        upd = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        p_new = p32 - opt["lr"] * (upd + opt["weight_decay"] * p32)
        return p_new.astype(p.dtype), m, v, g

    out = jax.tree_util.tree_map(one, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(           # noqa: E731
        lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), pick(3)


def leaf_norms(tree) -> jax.Array:
    """[n_leaves] float32 norms, in ``tree_leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def local_step(a: Arch, opt: dict, fp8: bool, t, params, m, v, batch):
    """One node's step: (params, m, v, loss, clipped-grad leaf norms)."""
    lval, grads = jax.value_and_grad(
        lambda p: loss(a, fp8, p, batch["tokens"], batch["labels"]))(params)
    p, m, v, g = adamw(opt, t, params, grads, m, v)
    return p, m, v, lval, leaf_norms(g)


def ring_round(mix: dict, params, lam, bar_prev, eta_up, eta_down,
               exchange: bool = True):
    """One consensus round on a J-node ring (neighbours i+1 and i-1).

    ``params`` carry a leading node axis; ``lam`` and ``bar_prev`` (the
    dual and the previous neighbour mean) are float32 trees like them;
    ``eta_up[i]``, ``eta_down[i]`` are node i's penalties on its edges to
    i+1 and i-1. Each edge weighs by the mean of its two ends' penalties.
    Returns (params, lam, bar, r [J], s [J]).
    """
    prox, deg = mix["prox_step"], 2
    e_up = 0.5 * (eta_up + jnp.roll(eta_down, -1))
    e_down = 0.5 * (eta_down + jnp.roll(eta_up, 1))
    sym_sum = e_up + e_down
    alpha = prox / (1.0 + 2.0 * sym_sum)
    eta_node = sym_sum / deg
    r_sq = s_sq = 0.0
    new, lams, bars = [], [], []
    for p, l, b in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (params, lam, bar_prev))):
        col = lambda v: v.reshape((-1,) + (1,) * (p.ndim - 1))  # noqa: E731
        x32 = p.astype(jnp.float32)
        up, down = (jnp.roll(x32, -1, axis=0), jnp.roll(x32, 1, axis=0)) \
            if exchange else (x32, x32)
        nbr = (col(e_up) * up + col(e_down) * down) / col(sym_sum)
        bar = (up + down) / deg
        theta_new = x32 - col(alpha) * (2.0 * l + col(sym_sum) * (x32 - nbr))
        lams.append(l + 0.5 * col(sym_sum) * (theta_new - nbr))
        axes = tuple(range(1, p.ndim))
        r_sq = r_sq + jnp.sum((theta_new - bar) ** 2, axis=axes)
        s_sq = s_sq + eta_node ** 2 * jnp.sum((bar - b) ** 2, axis=axes)
        new.append(theta_new.astype(p.dtype))
        bars.append(bar)
    tdef = jax.tree_util.tree_structure(params)
    return (jax.tree_util.tree_unflatten(tdef, new),
            jax.tree_util.tree_unflatten(tdef, lams),
            jax.tree_util.tree_unflatten(tdef, bars),
            jnp.sqrt(r_sq), jnp.sqrt(s_sq))


def nap_first_eta(eta0: float, f_self, f_up, f_down):
    """Each node's edge penalties after the first round of NAP.

    ``f_up[i]`` is node i's probe loss at node i+1's parameters, ``f_down``
    at i-1's. The probes are normalised over the node's neighbourhood
    (kappa in [1, 2]) and tau = kappa_self / kappa_neighbour - 1; within
    the first round every edge is within its budget, so eta = eta0 (1 +
    tau). Returns (eta_up, eta_down), each [J].
    """
    lo = jnp.minimum(f_self, jnp.minimum(f_up, f_down))
    hi = jnp.maximum(f_self, jnp.maximum(f_up, f_down))
    span = jnp.maximum(hi - lo, jnp.finfo(jnp.float32).tiny)
    kappa = lambda f: (f - lo) / span + 1.0              # noqa: E731
    tau = lambda f: kappa(f_self) / jnp.maximum(kappa(f), 1.0) - 1.0  # noqa
    return eta0 * (1.0 + tau(f_up)), eta0 * (1.0 + tau(f_down))
