"""Shared building blocks: norms, RoPE, SwiGLU MLP, embeddings."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models.params import ParamDef


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_table(seq_len: int, head_dim: int, theta: float,
               dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]          # [S, half]
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, hd]; cos/sin: [S, hd//2] (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------- MLP ------
def mlp_defs(cfg: ArchConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wi_gate": ParamDef((d, f), ("fsdp", "mlp"), dtype),
        "wi_up": ParamDef((d, f), ("fsdp", "mlp"), dtype),
        "wo": ParamDef((f, d), ("mlp", "fsdp"), dtype),
    }


def mlp_apply(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])
    h = shard(h, "batch", None, "mlp")
    return h @ p["wo"]


# ----------------------------------------------------------- embeddings -----
def embed_defs(cfg: ArchConfig, dtype) -> dict:
    # a tied table is also the output head: at unit scale its logits would
    # have std sqrt(d_model), so it takes the published init std 0.02
    init = "normal" if cfg.tie_embeddings else "embed"
    out = {"embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "fsdp"),
                             dtype, init=init, scale=0.02)}
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamDef((cfg.d_model, cfg.vocab),
                                  ("fsdp", "vocab"), dtype)
    return out


def embed_tokens(p: dict, tokens: jax.Array) -> jax.Array:
    return shard(jnp.take(p["embed"], tokens, axis=0), "batch", None, None)


def unembed(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    return shard(x @ w, "batch", None, "vocab")
