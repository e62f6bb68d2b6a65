"""Pallas chunked WKV6 scan — the RWKV6 recurrence as TPU matmuls.

The per-step recurrence
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T);   S_t = diag(w_t) S_{t-1} + k_t v_t^T
is O(T) sequential. The TPU-native adaptation blocks time into chunks of C
steps and turns the inner work into MXU matmuls (the standard linear-attention
chunking, re-derived for RWKV's per-channel decay):

with log-decays lw_t and  ls_t = sum_{j<t} lw_j  (exclusive cumsum within the
chunk), P = exp(ls_C) the full-chunk decay:

    y      = ((r*exp(ls)) @ S_in^T ... inter-chunk term)      [C, hd_v]
           + ((r_i . k_l * exp(ls_i - ls_{l+1}))_{l<i} + diag(r_i . u k_i)) @ v
    S_out  = diag(P) S_in + (k * exp(lsC - ls_{l+1}))^T @ v

All ratios are exp of non-positive differences => numerically safe.
Grid: (B, H, T/C) with the chunk axis sequential ("arbitrary"), S carried in
a [hd, hd] f32 VMEM scratch. Chunk C and head dim are the VMEM tile knobs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[...].astype(jnp.float32)

    r = r_ref[...].astype(jnp.float32)      # [C, hd]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lw = lw_ref[...].astype(jnp.float32)    # log decay, <= 0
    u = u_ref[...].astype(jnp.float32)      # [1, hd] bonus

    ls = jnp.cumsum(lw, axis=0) - lw        # exclusive cumsum  [C, hd]
    ls_total = ls[-1] + lw[-1]              # [hd] full-chunk log decay
    s_in = s_ref[...]                       # [hd, hd] (key x value)

    # inter-chunk: y_i += (r_i * exp(ls_i)) @ S_in       (exp(ls) <= 1: safe)
    r_s = r * jnp.exp(ls)
    y = jax.lax.dot_general(r_s, s_in, (((1,), (0,)), ((), ())))

    # intra-chunk: A[i, l] = sum_d r_i exp(ls_i - ls_{l+1}) k_l   (l < i).
    # The factored form exp(ls_i) * exp(-ls_{l+1}) overflows for strong decay
    # x long chunks; re-center both exponentials at half the chunk decay so
    # each factor stays within float32 range (|ls - c| <= |ls_total|/2).
    c = 0.5 * ls_total[None, :]
    r_dec = r * jnp.exp(ls - c)
    k_dec = k * jnp.exp(c - (ls + lw))      # k_l * exp(c - ls_{l+1})
    a = jax.lax.dot_general(r_dec, k_dec, (((1,), (1,)), ((), ())))
    ii = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    ll = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    a = jnp.where(ll < ii, a, 0.0)
    # current-step bonus: diag term r_i . (u * k_i)
    diag = jnp.sum(r * u * k, axis=1)
    a = a + jnp.where(ll == ii, diag[:, None], 0.0)
    y = y + jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())))
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: S_out = diag(P) S_in + (k * exp(lsC - ls_{l+1}))^T @ v
    k_carry = k * jnp.exp(ls_total[None, :] - (ls + lw))
    s_new = jnp.exp(ls_total)[:, None] * s_in + jax.lax.dot_general(
        k_carry, v, (((0,), (0,)), ((), ())))
    s_ref[...] = s_new

    @pl.when(ci == num_chunks - 1)
    def _final():
        sout_ref[...] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, log_w, u, s0, *, chunk: int = 32,
               interpret: bool):
    """r,k,v,log_w: [B, H, T, hd]; u: [H, hd]; s0: [B, H, hd, hd].

    Returns (y [B, H, T, hd], s_final [B, H, hd, hd]).
    """
    b, h, t, hd = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    num_chunks = t // chunk
    grid = (b, h, num_chunks)

    kernel = functools.partial(_kernel, chunk=chunk, num_chunks=num_chunks)
    seq_spec = pl.BlockSpec((None, None, chunk, hd),
                            lambda bb, hh, ci: (bb, hh, ci, 0))
    y, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((None, 1, hd), lambda bb, hh, ci: (hh, 0, 0)),
            pl.BlockSpec((None, None, hd, hd),
                         lambda bb, hh, ci: (bb, hh, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((None, None, hd, hd),
                         lambda bb, hh, ci: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u[:, None, :], s0)
    return y, s_out
