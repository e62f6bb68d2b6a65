"""Pallas fused consensus-round kernels — the ADMM hot loop in one HBM pass.

One ADMM consensus round touches every parameter ~6 times when written
naively (prox pull, dual update, two residual reductions, two neighbor
means) plus one more full pass to dequantize an int8 wire payload. The math
is all elementwise over the flattened parameter vector, so it is purely
memory-bound: fusing it into a single kernel takes the round from ~7 HBM
passes to one read per operand + one write per result.

Two entry points:

  * ``consensus_update`` — the original per-vector kernel (prox pull + dual
    update + both residual partials; neighbor means precomputed upstream).
    Kept as the simple building block and oracle target.
  * ``consensus_round`` — the flat-buffer engine kernel: takes the raw
    *rolled wire payloads* for every graph offset (int8/fp8 or float) and
    fuses dequantization, both neighbor means, prox pull, dual update and
    both residual reductions. Per-node scalars (alpha, eta_sum, eta_node),
    the per-offset edge weights and the per-offset dequant scales ride in
    SMEM. Scale granularity is codec-parameterized
    (``repro.wire.DequantSpec``): per-(node, leaf) scales resolve through
    the block->leaf table (the int8 wire), per-(node, BLOCK) scales (the
    fp8 wires) index by the block's own program id — no table lookup, and
    the scale rows shard with the slabs on the sharded engine.

Per block of the flat parameter vector (``consensus_round``):
    nbr_w     = sum_d e_sym[d] * dequant(wire[d])
    bar       = sum_d dequant(wire[d]) / deg
    nbr_avg   = nbr_w / max(eta_sum, eps)
    theta_new = theta - alpha (2 lam + eta_sum (theta - nbr_avg))
    lam_new   = lam + 0.5 eta_sum (theta_new - nbr_avg)
    r_sq     += |theta_new - bar|^2                      (per-block partials)
    s_sq     += eta_node^2 |bar - bar_prev|^2

Dynamic topology (``bar_w``/``inv_deg`` supplied — see ``repro.topology``):
the traced per-(offset, node) edge gate ``bar_w`` weights the neighbor-mean
accumulation and the per-node ``inv_deg`` (1 / active degree) replaces the
static 1/deg, so a gated edge contributes exactly zero math. The ungated
path is byte-for-byte the PR 1 kernel — ``scheduler="static"`` stays
bit-identical by construction.

Zero-kick gating (``kick_w`` supplied, masked variants only): when the
scheduler gates an edge, its final consensus force ``w_ij (theta_i -
theta_j)`` is absorbed into the dual — one extra dual-ascent step
restricted to the newly-gated edges — so removing the edge leaves every
node's augmented stationarity unchanged at the current iterate.
``kick_w[d, i]`` is the symmetrized penalty weight of the newly-gated edge
(zero elsewhere); ``theta_j`` is the edge's wire payload in the same call
(the engine delays scheduler kicks one round so the payload is on the
wire; the async executor kicks staleness-gated edges in-round from its
ledger). The kick term is compiled only when the scheduler can gate
(``TopologyConfig.can_gate``): a lam + 0.0 would flip -0.0 bits and break
the static-path bit-identity pin.

SMEM footprint note: the block->leaf table costs 4 bytes per block — pick
``block_size`` >= 64k at LM scale so a multi-billion-parameter vector keeps
the table in the tens of KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad1(x, padded):
    (n,) = x.shape
    return x if padded == n else jnp.pad(x, (0, padded - n))


def _kernel(scalars_ref, theta_ref, lam_ref, nbr_ref, bar_ref, barp_ref,
            theta_out, lam_out, rsq_out, ssq_out):
    eta_sum = scalars_ref[0]
    eta_node = scalars_ref[1]
    step = scalars_ref[2]
    theta = theta_ref[...].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)
    nbr = nbr_ref[...].astype(jnp.float32)
    bar = bar_ref[...].astype(jnp.float32)
    barp = barp_ref[...].astype(jnp.float32)

    theta_new = theta - step * (2.0 * lam + eta_sum * (theta - nbr))
    lam_new = lam + 0.5 * eta_sum * (theta_new - nbr)
    theta_out[...] = theta_new.astype(theta_out.dtype)
    lam_out[...] = lam_new.astype(lam_out.dtype)
    rsq_out[0] = jnp.sum((theta_new - bar) ** 2)
    dbar = bar - barp
    ssq_out[0] = (eta_node * eta_node) * jnp.sum(dbar * dbar)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "interpret"))
def consensus_update(theta, lam, nbr_avg, theta_bar, theta_bar_prev, *,
                     eta_sum, eta_node, step_size,
                     block_size: int = 65536, interpret: bool):
    """All tensor args are flat [N] vectors; N need NOT be a block multiple.

    Non-multiple N is zero-padded internally: zero inputs are a fixed point
    of the update (theta_new = lam_new = 0) and contribute exactly 0 to both
    residual reductions, so the padded sums equal the masked ones.

    Returns (theta_new [N], lam_new [N], r_sq scalar, s_sq scalar).
    """
    (n,) = theta.shape
    block_size = min(block_size, n)
    padded = -(-n // block_size) * block_size
    args = [_pad1(x, padded)
            for x in (theta, lam, nbr_avg, theta_bar, theta_bar_prev)]
    grid = (padded // block_size,)
    scalars = jnp.stack([jnp.asarray(eta_sum, jnp.float32),
                         jnp.asarray(eta_node, jnp.float32),
                         jnp.asarray(step_size, jnp.float32)])

    vec = pl.BlockSpec((block_size,), lambda i: (i,))
    theta_new, lam_new, rsq, ssq = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            vec, vec, vec, vec, vec,
        ],
        out_specs=[
            vec, vec,
            pl.BlockSpec((1,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded,), theta.dtype),
            jax.ShapeDtypeStruct((padded,), lam.dtype),
            jax.ShapeDtypeStruct(grid, jnp.float32),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        interpret=interpret,
    )(scalars, *args)
    return theta_new[:n], lam_new[:n], rsq.sum(), ssq.sum()


def _write_partials(part_out, rsq, ssq):
    """Both residual partials of one block as one lane-dense (1, 128) row:
    lane 0 holds r_sq, lane 1 holds s_sq (Mosaic refuses (1, 1) blocks)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    part_out[0, 0] = jnp.where(lane == 0, rsq,
                               jnp.where(lane == 1, ssq, 0.0))


def _round_kernel(deg, per_block, block_leaf_ref, node_ref, esym_ref,
                  scale_ref, theta_ref, lam_ref, barp_ref, wires_ref,
                  theta_out, lam_out, bar_out, part_out):
    i = pl.program_id(0)
    b = pl.program_id(1)
    # per-leaf scales resolve through the block->leaf table; per-block
    # scales (the fp8 codecs) index by the block id directly
    li = b if per_block else block_leaf_ref[b]
    alpha = node_ref[0, i]
    eta_sum = node_ref[1, i]
    eta_node = node_ref[2, i]

    theta = theta_ref[...].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)
    barp = barp_ref[...].astype(jnp.float32)

    nbr_w = jnp.zeros_like(theta)
    nbr_p = jnp.zeros_like(theta)
    for d in range(deg):                      # static unroll over offsets
        x = wires_ref[d].astype(jnp.float32) * scale_ref[d, i, li]
        nbr_w = nbr_w + esym_ref[d, i] * x
        nbr_p = nbr_p + x
    bar = nbr_p * (1.0 / deg)
    nbr = nbr_w / jnp.maximum(eta_sum, 1e-12)

    theta_new = theta - alpha * (2.0 * lam + eta_sum * (theta - nbr))
    lam_new = lam + 0.5 * eta_sum * (theta_new - nbr)
    theta_out[...] = theta_new.astype(theta_out.dtype)
    lam_out[...] = lam_new.astype(lam_out.dtype)
    bar_out[...] = bar.astype(bar_out.dtype)
    dbar = bar - barp
    _write_partials(part_out, jnp.sum((theta_new - bar) ** 2),
                    (eta_node * eta_node) * jnp.sum(dbar * dbar))


def _row_kernel(deg, block_size, per_block, block_leaf_ref, node_ref,
                esym_ref, scale_ref, theta_ref, lam_ref, barp_ref, wires_ref,
                theta_out, lam_out, bar_out, rsq_out, ssq_out):
    """Whole-row variant of ``_round_kernel`` (one grid step per node).

    Used in interpret mode, where there is no VMEM limit and the per-grid-
    step interpreter dispatch (~ms on CPU) would otherwise dominate: the
    8-block tiling that keeps the TPU kernel inside VMEM buys nothing under
    the interpreter. The math and the residual reduction ORDER (blockwise
    partial sums) are identical to the blocked kernel, so both variants
    match ``ref.consensus_round_ref`` to the same round-off.
    """
    alpha = node_ref[0, 0]
    eta_sum = node_ref[1, 0]
    eta_node = node_ref[2, 0]
    theta = theta_ref[0, :].astype(jnp.float32)
    lam = lam_ref[0, :].astype(jnp.float32)
    barp = barp_ref[0, :].astype(jnp.float32)

    bl = block_leaf_ref[...]
    nbr_w = jnp.zeros_like(theta)
    nbr_p = jnp.zeros_like(theta)
    for d in range(deg):
        row = scale_ref[d, 0, :] if per_block else scale_ref[d, 0, :][bl]
        scale_vec = jnp.repeat(row, block_size,
                               total_repeat_length=theta.shape[0])
        x = wires_ref[d, 0, :].astype(jnp.float32) * scale_vec
        nbr_w = nbr_w + esym_ref[d, 0] * x
        nbr_p = nbr_p + x
    bar = nbr_p * (1.0 / deg)
    nbr = nbr_w / jnp.maximum(eta_sum, 1e-12)

    theta_new = theta - alpha * (2.0 * lam + eta_sum * (theta - nbr))
    lam_new = lam + 0.5 * eta_sum * (theta_new - nbr)
    theta_out[0, :] = theta_new.astype(theta_out.dtype)
    lam_out[0, :] = lam_new.astype(lam_out.dtype)
    bar_out[0, :] = bar.astype(bar_out.dtype)

    def blocksum(v):                    # same order as the blocked kernel
        return v.reshape(-1, block_size).sum(axis=-1).sum()

    rsq_out[0, 0] = blocksum((theta_new - bar) ** 2)
    dbar = bar - barp
    ssq_out[0, 0] = (eta_node * eta_node) * blocksum(dbar * dbar)


def _round_kernel_masked(deg, has_kick, per_block, block_leaf_ref, node_ref,
                         esym_ref, barw_ref, *refs):
    """Edge-gated variant of ``_round_kernel`` (see module docstring)."""
    if has_kick:
        (kick_ref, scale_ref, theta_ref, lam_ref, barp_ref, wires_ref,
         theta_out, lam_out, bar_out, part_out) = refs
    else:
        (scale_ref, theta_ref, lam_ref, barp_ref, wires_ref,
         theta_out, lam_out, bar_out, part_out) = refs
    i = pl.program_id(0)
    b = pl.program_id(1)
    li = b if per_block else block_leaf_ref[b]
    alpha = node_ref[0, i]
    eta_sum = node_ref[1, i]
    eta_node = node_ref[2, i]
    inv_deg = node_ref[3, i]

    theta = theta_ref[...].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)
    barp = barp_ref[...].astype(jnp.float32)

    nbr_w = jnp.zeros_like(theta)
    nbr_p = jnp.zeros_like(theta)
    kick_x = jnp.zeros_like(theta)
    ksum = jnp.float32(0.0)
    for d in range(deg):                      # static unroll over offsets
        x = wires_ref[d].astype(jnp.float32) * scale_ref[d, i, li]
        nbr_w = nbr_w + esym_ref[d, i] * x
        nbr_p = nbr_p + barw_ref[d, i] * x
        if has_kick:
            kick_x = kick_x + kick_ref[d, i] * x
            ksum = ksum + kick_ref[d, i]
    bar = nbr_p * inv_deg
    nbr = nbr_w / jnp.maximum(eta_sum, 1e-12)

    theta_new = theta - alpha * (2.0 * lam + eta_sum * (theta - nbr))
    lam_new = lam + 0.5 * eta_sum * (theta_new - nbr)
    if has_kick:
        # zero-kick: absorb newly-gated edges' final consensus force
        # 0.5 sum_d kick_d (theta - x_d) into the dual (round-start iterate)
        lam_new = lam_new + 0.5 * (ksum * theta - kick_x)
    theta_out[...] = theta_new.astype(theta_out.dtype)
    lam_out[...] = lam_new.astype(lam_out.dtype)
    bar_out[...] = bar.astype(bar_out.dtype)
    dbar = bar - barp
    _write_partials(part_out, jnp.sum((theta_new - bar) ** 2),
                    (eta_node * eta_node) * jnp.sum(dbar * dbar))


def _row_kernel_masked(deg, block_size, has_kick, per_block, block_leaf_ref,
                       node_ref, esym_ref, barw_ref, *refs):
    """Edge-gated variant of ``_row_kernel`` (whole-row interpret tiling)."""
    if has_kick:
        (kick_ref, scale_ref, theta_ref, lam_ref, barp_ref, wires_ref,
         theta_out, lam_out, bar_out, rsq_out, ssq_out) = refs
    else:
        (scale_ref, theta_ref, lam_ref, barp_ref, wires_ref,
         theta_out, lam_out, bar_out, rsq_out, ssq_out) = refs
    alpha = node_ref[0, 0]
    eta_sum = node_ref[1, 0]
    eta_node = node_ref[2, 0]
    inv_deg = node_ref[3, 0]
    theta = theta_ref[0, :].astype(jnp.float32)
    lam = lam_ref[0, :].astype(jnp.float32)
    barp = barp_ref[0, :].astype(jnp.float32)

    bl = block_leaf_ref[...]
    nbr_w = jnp.zeros_like(theta)
    nbr_p = jnp.zeros_like(theta)
    kick_x = jnp.zeros_like(theta)
    ksum = jnp.float32(0.0)
    for d in range(deg):
        row = scale_ref[d, 0, :] if per_block else scale_ref[d, 0, :][bl]
        scale_vec = jnp.repeat(row, block_size,
                               total_repeat_length=theta.shape[0])
        x = wires_ref[d, 0, :].astype(jnp.float32) * scale_vec
        nbr_w = nbr_w + esym_ref[d, 0] * x
        nbr_p = nbr_p + barw_ref[d, 0] * x
        if has_kick:
            kick_x = kick_x + kick_ref[d, 0] * x
            ksum = ksum + kick_ref[d, 0]
    bar = nbr_p * inv_deg
    nbr = nbr_w / jnp.maximum(eta_sum, 1e-12)

    theta_new = theta - alpha * (2.0 * lam + eta_sum * (theta - nbr))
    lam_new = lam + 0.5 * eta_sum * (theta_new - nbr)
    if has_kick:
        lam_new = lam_new + 0.5 * (ksum * theta - kick_x)
    theta_out[0, :] = theta_new.astype(theta_out.dtype)
    lam_out[0, :] = lam_new.astype(lam_out.dtype)
    bar_out[0, :] = bar.astype(bar_out.dtype)

    def blocksum(v):                    # same order as the blocked kernel
        return v.reshape(-1, block_size).sum(axis=-1).sum()

    rsq_out[0, 0] = blocksum((theta_new - bar) ** 2)
    dbar = bar - barp
    ssq_out[0, 0] = (eta_node * eta_node) * blocksum(dbar * dbar)


def _row_round(theta, lam, bar_prev, wires, scales, e_sym, node_scalars,
               block_leaf_arr, *, block_size, interpret, bar_w=None,
               kick_w=None, scales_per_block=False):
    j, total = theta.shape
    deg = wires.shape[0]
    masked = bar_w is not None
    vec = pl.BlockSpec((1, total), lambda i: (i, 0))
    nscal = 4 if masked else 3
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),       # block -> leaf
        pl.BlockSpec((nscal, 1), lambda i: (0, i),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((deg, 1), lambda i: (0, i),
                     memory_space=pltpu.SMEM),
    ]
    args = [block_leaf_arr, node_scalars, e_sym.astype(jnp.float32)]
    if masked:
        in_specs.append(pl.BlockSpec((deg, 1), lambda i: (0, i),
                                     memory_space=pltpu.SMEM))
        args.append(bar_w.astype(jnp.float32))
    if kick_w is not None:
        in_specs.append(pl.BlockSpec((deg, 1), lambda i: (0, i),
                                     memory_space=pltpu.SMEM))
        args.append(kick_w.astype(jnp.float32))
    in_specs += [
        pl.BlockSpec((deg, 1, scales.shape[-1]), lambda i: (0, i, 0),
                     memory_space=pltpu.SMEM),
        vec, vec, vec,
        pl.BlockSpec((deg, 1, total), lambda i: (0, i, 0)),
    ]
    args += [scales.astype(jnp.float32), theta, lam, bar_prev, wires]
    alias_base = len(in_specs) - 4                    # position of theta
    kernel = (functools.partial(_row_kernel_masked, deg, block_size,
                                kick_w is not None, scales_per_block)
              if masked
              else functools.partial(_row_kernel, deg, block_size,
                                     scales_per_block))
    return pl.pallas_call(
        kernel,
        grid=(j,),
        in_specs=in_specs,
        name="consensus_round",
        out_specs=[vec, vec, vec,
                   pl.BlockSpec((1, 1), lambda i: (i, 0)),
                   pl.BlockSpec((1, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((j, total), theta.dtype),
            jax.ShapeDtypeStruct((j, total), lam.dtype),
            jax.ShapeDtypeStruct((j, total), jnp.float32),
            jax.ShapeDtypeStruct((j, 1), jnp.float32),
            jax.ShapeDtypeStruct((j, 1), jnp.float32),
        ],
        input_output_aliases={alias_base: 0, alias_base + 1: 1,
                              alias_base + 2: 2},
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("block_leaf", "block_size",
                                             "interpret", "whole_rows",
                                             "scales_per_block"))
def consensus_round(theta, lam, bar_prev, wires, scales, e_sym,
                    alpha, eta_sum, eta_node, *,
                    block_leaf: tuple[int, ...] | None, block_size: int,
                    interpret: bool,
                    whole_rows: bool | None = None,
                    bar_w=None, inv_deg=None, kick_w=None,
                    block_leaf_arr=None, scales_per_block: bool = False):
    """Whole-round fused kernel over the flat buffer.

    Args:
      theta, lam, bar_prev: [J, total] float buffers (total = blocks * bs).
      wires: [deg, J, total] rolled wire payloads — int8/fp8 (quantized) or
        any float dtype; row d holds theta_{(i+off_d) % J} at node i.
      scales: [deg, J, L] f32 per-leaf dequant scales (ones when the wire is
        uncompressed) — or, with ``scales_per_block``, [deg, J, num_blocks]
        per-BLOCK scales on the layout's block grid (the fp8 codecs).
      e_sym: [deg, J] f32 symmetrized per-edge penalties eta_sym_ij
        (edge-gated upstream for dynamic topologies: zero on masked edges).
      alpha, eta_sum, eta_node: [J] f32 per-node scalars.
      block_leaf: static tuple, owning leaf id per block (FlatLayout table).
      block_size: elements per block; must divide total.
      bar_w: optional [deg, J] f32 traced edge gates (1 = active) weighting
        the neighbor-mean accumulation — the dynamic-topology mask.
      inv_deg: optional [J] f32, 1 / active degree (0 for isolated/ghost
        nodes). Must be supplied together with ``bar_w``; both None selects
        the ungated PR 1 kernel (byte-identical math).
      kick_w: optional [deg, J] f32 zero-kick weights (masked variants
        only): the dual additionally absorbs
        ``0.5 * sum_d kick_w[d] * (theta - dequant(wire[d]))`` — the final
        consensus force of edges gated since the last round. Passing None
        compiles the kick-free kernel (bit-identical to PR 2).
      block_leaf_arr: optional TRACED [num_blocks] int32 block->leaf table
        replacing the static ``block_leaf`` tuple (pass ``block_leaf=None``
        then). The sharded engine uses this: under shard_map every device
        runs the same program on a DIFFERENT slab of the flat axis, so its
        slab's table must be data, not program. The table was already fed
        to the kernel as an SMEM operand — only the tracing changes.
      scales_per_block: static — ``scales`` carries one scalar per BLOCK
        (the fp8 codecs' granularity, ``repro.wire.DequantSpec``) instead
        of one per leaf; block b dequants from ``scales[b]`` directly, no
        block->leaf lookup. Under the sharded engine the scale rows shard
        with the slabs, so the LOCAL block id still indexes correctly.
        False keeps the per-leaf path bit-identical.

    Returns (theta_new [J, total], lam_new [J, total], bar [J, total] f32,
             r_sq [J], s_sq [J]).

    The input buffers theta/lam/bar_prev are aliased to the outputs
    theta_new/lam_new/bar, so with donated jit arguments XLA updates them
    in place.

    ``whole_rows`` (default: follow ``interpret``) switches to one grid
    step per node row — the interpreter tiling, which Mosaic does not
    lower; the VMEM-sized blocked grid is what compiles for the TPU (and
    stays testable in interpret mode via ``whole_rows=False``).
    """
    j, total = theta.shape
    deg = wires.shape[0]
    assert total % block_size == 0, (total, block_size)
    nblocks = total // block_size
    assert (block_leaf is None) != (block_leaf_arr is None), \
        "exactly one of block_leaf / block_leaf_arr"
    masked = bar_w is not None
    assert masked == (inv_deg is not None), "bar_w and inv_deg travel together"
    assert kick_w is None or masked, "kick_w needs the masked kernel"

    rows = [jnp.asarray(alpha, jnp.float32),
            jnp.asarray(eta_sum, jnp.float32),
            jnp.asarray(eta_node, jnp.float32)]
    if masked:
        rows.append(jnp.asarray(inv_deg, jnp.float32))
    node_scalars = jnp.stack(rows)                    # [3|4, J]
    if block_leaf_arr is None:
        assert len(block_leaf) == nblocks, (len(block_leaf), nblocks)
        block_leaf_arr = jnp.asarray(block_leaf, jnp.int32)
    assert block_leaf_arr.shape == (nblocks,), (block_leaf_arr.shape, nblocks)
    if scales_per_block:
        assert scales.shape[-1] == nblocks, (scales.shape, nblocks)

    if whole_rows is None:
        whole_rows = interpret
    assert interpret or not whole_rows, \
        "the whole-row tiling is interpret-only; compiled runs use blocks"
    if whole_rows:
        tn, ln, bar, rsq, ssq = _row_round(
            theta, lam, bar_prev, wires, scales, e_sym, node_scalars,
            block_leaf_arr, block_size=block_size, interpret=interpret,
            bar_w=bar_w, kick_w=kick_w, scales_per_block=scales_per_block)
        return tn, ln, bar, rsq[:, 0], ssq[:, 0]

    # Mosaic tiling: the row blocks are (1, block_size), legal because one
    # node row is the whole second-minor dim (the trainer's shard_map hands
    # each device J = 1); the per-node scalars, edge weights and dequant
    # scales are whole SMEM operands indexed by the grid position; the
    # residual partials leave as lane-dense (1, 128) rows per block.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vec = pl.BlockSpec((1, block_size), lambda i, b: (i, b))
    wire_spec = pl.BlockSpec((deg, 1, block_size), lambda i, b: (0, i, b))
    part = pl.BlockSpec((1, 1, 1, 128), lambda i, b: (i, b, 0, 0))

    # block -> leaf table, per-node scalars, e_sym
    in_specs = [smem, smem, smem]
    args = [block_leaf_arr, node_scalars, e_sym.astype(jnp.float32)]
    if masked:
        in_specs.append(smem)                         # edge gates
        args.append(bar_w.astype(jnp.float32))
    if kick_w is not None:
        in_specs.append(smem)                         # zero-kick weights
        args.append(kick_w.astype(jnp.float32))
    in_specs += [
        smem,                                         # dequant scales
        vec, vec, vec,               # theta, lam, bar_prev
        wire_spec,
    ]
    args += [scales.astype(jnp.float32), theta, lam, bar_prev, wires]
    ab = len(in_specs) - 4                            # position of theta

    kernel = (functools.partial(_round_kernel_masked, deg,
                                kick_w is not None, scales_per_block)
              if masked
              else functools.partial(_round_kernel, deg, scales_per_block))
    theta_new, lam_new, bar, parts = pl.pallas_call(
        kernel,
        grid=(j, nblocks),
        in_specs=in_specs,
        # a fixed name: the benchmark's trace readers find the kernel by it
        name="consensus_round",
        out_specs=[vec, vec, vec, part],
        out_shape=[
            jax.ShapeDtypeStruct((j, total), theta.dtype),
            jax.ShapeDtypeStruct((j, total), lam.dtype),
            jax.ShapeDtypeStruct((j, total), jnp.float32),
            jax.ShapeDtypeStruct((j, nblocks, 1, 128), jnp.float32),
        ],
        # in-place: theta->theta_new, lam->lam_new, bar_prev->bar
        input_output_aliases={ab: 0, ab + 1: 1, ab + 2: 2},
        interpret=interpret,
    )(*args)
    return (theta_new, lam_new, bar, parts[:, :, 0, 0].sum(axis=1),
            parts[:, :, 0, 1].sum(axis=1))
