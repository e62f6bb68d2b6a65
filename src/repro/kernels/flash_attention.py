"""Pallas flash attention (causal / sliding-window, GQA-aware).

TPU-native tiling: queries blocked [block_q, head_dim] in VMEM, K/V streamed
in [block_k, head_dim] tiles along the innermost (sequential) grid axis with
the online-softmax accumulators (m, l, acc) carried in VMEM scratch. MXU work
is the two [block_q, block_k] x [block_k, head_dim] matmuls per tile; fully
masked tiles (beyond the causal diagonal or the sliding window) are skipped
with ``pl.when``.

Layout: [B, H, S, hd] head-major. GQA is expressed in the K/V index_map
(query head h reads KV head h // n_rep) so KV tiles are never materialized
per query head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, causal: bool, window: int,
            num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # tile visibility: skip tiles fully above the causal diagonal or fully
    # left of the sliding window
    pred = ki >= 0
    if causal:
        pred &= k_start <= q_start + block_q - 1
    if window > 0:
        pred &= k_start + block_k - 1 > q_start - window

    @pl.when(pred)
    def _compute():
        q = q_ref[...].astype(jnp.float32)              # [bq, hd]
        k = k_ref[...].astype(jnp.float32)              # [bk, hd]
        v = v_ref[...].astype(jnp.float32)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                              # [bq]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_cur

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool) -> jax.Array:
    """q: [B, H, S, hd]; k, v: [B, K, S, hd] with H = K * n_rep."""
    b, h, s, hd = q.shape
    kheads = k.shape[1]
    assert h % kheads == 0, (h, kheads)
    n_rep = h // kheads
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    num_k_blocks = s // block_k

    grid = (b, h, s // block_q, num_k_blocks)
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, num_k_blocks=num_k_blocks)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, qi, ki, n_rep=n_rep:
                         (bb, hh // n_rep, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda bb, hh, qi, ki, n_rep=n_rep:
                         (bb, hh // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # m: running max
            pltpu.VMEM((block_q,), jnp.float32),       # l: running sum
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc: running output
        ],
        interpret=interpret,
    )(q, k, v)
