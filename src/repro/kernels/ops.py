"""Jit'd public wrappers for the Pallas kernels.

The mode follows the backend and nothing else: compiled Mosaic on a TPU,
Pallas interpret mode (kernel body evaluated with plain HLO ops — jit and
shard_map traceable) on every other backend. There is no override, so a
TPU run can never fall back to the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import consensus_update as _cu
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _rw


def interpret_mode() -> bool:
    """True unless the default backend is a TPU (compiled Mosaic there)."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """Model-layout wrapper: q [B,S,H,hd], k/v [B,S,K,hd] -> [B,S,H,hd]."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret_mode())
    return jnp.swapaxes(out, 1, 2)


def flash_attention_hmajor(q, k, v, **kw):
    """Head-major passthrough: q [B,H,S,hd]."""
    return _fa.flash_attention(q, k, v, interpret=interpret_mode(), **kw)


def rwkv6_scan(r, k, v, w, u, s0, *, chunk: int = 32):
    """Model-layout wrapper: r/k/v/w [B,S,H,hd] (w = decay in (0,1))."""
    rt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (r, k, v))
    log_w = jnp.log(jnp.maximum(jnp.swapaxes(w, 1, 2), 1e-38))
    y, s = _rw.rwkv6_scan(rt, kt, vt, log_w, u, s0, chunk=chunk,
                          interpret=interpret_mode())
    return jnp.swapaxes(y, 1, 2), s


def consensus_update(theta, lam, nbr_avg, theta_bar, theta_bar_prev, *,
                     eta_sum, eta_node, step_size, block_size: int = 65536):
    return _cu.consensus_update(theta, lam, nbr_avg, theta_bar,
                                theta_bar_prev, eta_sum=eta_sum,
                                eta_node=eta_node, step_size=step_size,
                                block_size=block_size, interpret=interpret_mode())


def consensus_round(theta, lam, bar_prev, wires, scales, e_sym,
                    alpha, eta_sum, eta_node, *, block_leaf, block_size,
                    whole_rows: bool | None = None,
                    bar_w=None, inv_deg=None, kick_w=None,
                    block_leaf_arr=None, scales_per_block: bool = False):
    """Whole-round fused flat-buffer kernel (see consensus_update module).

    ``bar_w``/``inv_deg`` select the edge-gated dynamic-topology variant;
    ``kick_w`` additionally compiles the zero-kick dual absorption.
    ``block_leaf_arr`` (traced) replaces the static ``block_leaf`` tuple on
    the sharded engine path (per-device slab tables).
    ``scales_per_block`` selects the per-BLOCK dequant granularity of the
    fp8 wire codecs (``repro.wire``) instead of the per-leaf table lookup.
    """
    return _cu.consensus_round(theta, lam, bar_prev, wires, scales, e_sym,
                               alpha, eta_sum, eta_node,
                               block_leaf=(None if block_leaf is None
                                           else tuple(block_leaf)),
                               block_size=block_size,
                               interpret=interpret_mode(),
                               whole_rows=whole_rows,
                               bar_w=bar_w, inv_deg=inv_deg, kick_w=kick_w,
                               block_leaf_arr=block_leaf_arr,
                               scales_per_block=scales_per_block)
