"""Mixture-of-Experts FFN with expert parallelism.

Three execution paths, one math:

  * ``moe_ref``        — dense masked reference (every expert on every token,
                         weighted by the routing mask). O(E/topk) extra FLOPs;
                         used for correctness tests and tiny smoke configs.
  * ``moe_apply`` a2a  — production path, shard_map over the mesh: tokens
                         (sharded batch x seq) are routed with a fixed-capacity
                         all_to_all along the ``model`` (expert) axis, computed
                         with ``lax.ragged_dot`` on the owning shard, and
                         returned. Matches DeepSeek/Moonlight-style EP on TPU.
  * ``moe_apply`` repl — decode path: tokens replicated over the expert axis,
                         each shard computes only its own experts' pairs and
                         the combine is a psum. (batch 128 cannot shard over
                         the model axis, so a2a dispatch would be degenerate.)

Routing: softmax gate, top-k, renormalized top-k weights (Moonlight/Kimi
convention). Overflowing tokens beyond the capacity factor are dropped
(weight zero), the standard TPU fixed-shape compromise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import current_mesh, shard_map
from repro.models.params import ParamDef


def moe_defs(cfg: ArchConfig, dtype) -> dict:
    d = cfg.d_model
    e = cfg.moe
    return {
        "router": ParamDef((d, e.num_experts), (None, None), dtype,
                           scale=0.02),
        "wg": ParamDef((e.num_experts, d, e.expert_d_ff),
                       ("experts", "fsdp", None), dtype),
        "wu": ParamDef((e.num_experts, d, e.expert_d_ff),
                       ("experts", "fsdp", None), dtype),
        "wd": ParamDef((e.num_experts, e.expert_d_ff, d),
                       ("experts", None, "fsdp"), dtype),
    }


def _route(cfg: ArchConfig, router_w: jax.Array, x: jax.Array):
    """x: [T, D] -> (top-k ids [T,k], renormalized weights [T,k])."""
    gates = jax.nn.softmax(
        (x @ router_w.astype(x.dtype)).astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(gates, cfg.moe.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return top_i.astype(jnp.int32), top_w.astype(x.dtype)


def moe_ref(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    """Dense reference. x: [B, S, D]."""
    b, s, d = x.shape
    e = cfg.moe
    xt = x.reshape(-1, d)
    top_i, top_w = _route(cfg, p["router"], xt)
    # mask[t, ex] = combined weight of expert ex for token t
    mask = jnp.zeros((xt.shape[0], e.num_experts), x.dtype)
    mask = mask.at[jnp.arange(xt.shape[0])[:, None], top_i].add(top_w)
    h = jnp.einsum("td,edf->tef", xt, p["wg"])
    u = jnp.einsum("td,edf->tef", xt, p["wu"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["wd"])
    return jnp.einsum("ted,te->td", y, mask).reshape(b, s, d)


# ------------------------------------------------------------------ EP ------
def _expert_ffn_ragged(wg, wu, wd, x_sorted, group_sizes):
    h = jax.lax.ragged_dot(x_sorted, wg, group_sizes)
    u = jax.lax.ragged_dot(x_sorted, wu, group_sizes)
    return jax.lax.ragged_dot(jax.nn.silu(h) * u, wd, group_sizes)


def _dispatch_local(cfg, x_flat, top_i, top_w, ep, e_local, capacity):
    """Slot assignment for fixed-capacity dispatch. Returns buffers+plan."""
    t_loc, d = x_flat.shape
    k = cfg.moe.top_k
    pair_tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), k)  # [P]
    pair_exp = top_i.reshape(-1)                                   # [P]
    pair_w = top_w.reshape(-1)
    pair_dest = pair_exp // e_local                                # dest shard
    order = jnp.argsort(pair_dest, stable=True)
    sdest = pair_dest[order]
    counts = jnp.bincount(pair_dest, length=ep)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(sdest.shape[0], dtype=jnp.int32) - starts[sdest]
    ok = rank < capacity
    slot_d = jnp.where(ok, sdest, 0)
    slot_c = jnp.where(ok, rank, 0)
    # scatter tokens + metadata into the send buffers (drop overflow)
    buf = jnp.zeros((ep, capacity, d), x_flat.dtype)
    meta = jnp.zeros((ep, capacity), jnp.int32)          # local expert id
    src_tok = pair_tok[order]
    buf = buf.at[slot_d, slot_c].set(
        jnp.where(ok[:, None], x_flat[src_tok], 0.0))
    meta = meta.at[slot_d, slot_c].set(
        jnp.where(ok, pair_exp[order] % e_local, 0))
    # plan for the combine: where each (token,k) pair's result lives
    plan = {
        "dest": slot_d, "slot": slot_c, "tok": src_tok,
        "w": jnp.where(ok, pair_w[order], 0.0),
    }
    return buf, meta, plan


def _moe_shard_a2a(cfg, ep_axis):
    """Build the per-shard function for the sharded-tokens (a2a) path."""
    e = cfg.moe

    def fn(router_w, wg, wu, wd, x):
        b, s, d = x.shape
        x_flat = x.reshape(-1, d)
        t_loc = x_flat.shape[0]
        ep = jax.lax.axis_size(ep_axis)
        e_local = e.num_experts // ep
        capacity = max(e.top_k, int(t_loc * e.top_k / ep
                                    * e.capacity_factor))
        top_i, top_w = _route(cfg, router_w, x_flat)
        buf, meta, plan = _dispatch_local(cfg, x_flat, top_i, top_w, ep,
                                          e_local, capacity)
        # exchange: row d of buf goes to shard d; we receive rows from all
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        meta = jax.lax.all_to_all(meta, ep_axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        recv = buf.reshape(-1, d)                 # [ep*capacity, D]
        ids = meta.reshape(-1)
        order = jnp.argsort(ids, stable=True)
        x_sorted = recv[order]
        group_sizes = jnp.bincount(ids, length=e_local)
        y_sorted = _expert_ffn_ragged(wg, wu, wd, x_sorted, group_sizes)
        y = jnp.zeros_like(y_sorted).at[order].set(y_sorted)
        y = y.reshape(ep, capacity, d)
        y = jax.lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                               tiled=True)
        # combine on the source shard
        vals = y[plan["dest"], plan["slot"]] * plan["w"][:, None]
        out = jax.ops.segment_sum(vals, plan["tok"], num_segments=t_loc)
        return out.reshape(b, s, d).astype(x.dtype)

    return fn


def _moe_shard_repl(cfg, ep_axis):
    """Per-shard function for the replicated-tokens (decode) path."""
    e = cfg.moe

    def fn(router_w, wg, wu, wd, x):
        b, s, d = x.shape
        x_flat = x.reshape(-1, d)
        t_loc = x_flat.shape[0]
        ep = jax.lax.axis_size(ep_axis)
        e_local = e.num_experts // ep
        my = jax.lax.axis_index(ep_axis)
        top_i, top_w = _route(cfg, router_w, x_flat)
        pair_tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32),
                              e.top_k)
        pair_exp = top_i.reshape(-1)
        pair_w = top_w.reshape(-1)
        mine = (pair_exp // e_local) == my
        local_id = jnp.where(mine, pair_exp % e_local, e_local - 1)
        w = jnp.where(mine, pair_w, 0.0)
        order = jnp.argsort(local_id, stable=True)
        x_sorted = x_flat[pair_tok[order]]
        # non-mine pairs were binned into expert e_local-1; they compute but
        # combine with weight zero (fixed-shape compromise, same as capacity)
        group_sizes = jnp.bincount(local_id, length=e_local)
        y_sorted = _expert_ffn_ragged(wg, wu, wd, x_sorted, group_sizes)
        vals = y_sorted * w[order][:, None]
        out = jax.ops.segment_sum(vals, pair_tok[order],
                                  num_segments=t_loc)
        out = jax.lax.psum(out, ep_axis)
        return out.reshape(b, s, d).astype(x.dtype)

    return fn


def moe_apply(cfg: ArchConfig, p: dict, x: jax.Array, *,
              decode: bool = False) -> jax.Array:
    """Dispatching MoE entry point. x: [B, S, D].

    Uses the ambient (possibly partially-manual) mesh: when called inside the
    consensus trainer's pod-manual region, only the still-auto data/model
    axes are mapped here; standalone, it maps batch axes too.
    """
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] == 1 \
            or cfg.moe.num_experts % mesh.shape["model"] != 0:
        return moe_ref(cfg, p, x)

    abstract = jax.sharding.get_abstract_mesh()
    if abstract.shape_tuple:
        manual_already = {name for name, ty in
                          zip(abstract.axis_names, abstract.axis_types)
                          if str(ty) == "Manual"}
        run_mesh = abstract
    else:
        manual_already = set()
        run_mesh = mesh

    from repro.distributed.sharding import logical_to_spec
    batch_rule = logical_to_spec(("batch",))[0] or ()
    if isinstance(batch_rule, str):
        batch_rule = (batch_rule,)
    batch_axes = tuple(a for a in batch_rule if a not in manual_already)

    if decode:
        x_spec = P(batch_axes if batch_axes else None, None, None)
        fn = _moe_shard_repl(cfg, "model")
        out_spec = x_spec
    else:
        x_spec = P(batch_axes if batch_axes else None, "model", None)
        fn = _moe_shard_a2a(cfg, "model")
        out_spec = x_spec
    w_spec = P("model", None, None)
    # manual over ALL remaining mesh axes: jax.grad of a shard_map that is
    # manual over a strict subset of axes miscompiles in XLA
    # (hlo_instruction.cc "Invalid binary instruction opcode copy");
    # axes not used in specs are simply replicated-manual.
    axis_names = set(run_mesh.axis_names) - manual_already
    return shard_map(
        fn, run_mesh,
        in_specs=(P(None, None), w_spec, w_spec, w_spec, x_spec),
        out_specs=out_spec,
        manual_axes=axis_names,
    )(p["router"], p["wg"], p["wu"], p["wd"], x)
