"""Operations and bytes, from shapes: model FLOPs and the round's bytes."""
from __future__ import annotations

from chipbench.reference import Arch


def matmul_params(a: Arch) -> int:
    """Weights that multiply activations: every layer's projections and
    MLP, and the head (the tied embedding counts once, as the head; an
    untied embedding table is a lookup and does not count)."""
    attn = a.d * a.hd * (2 * a.heads + 2 * a.kv)
    mlp = 3 * a.d * a.ff
    return a.layers * (attn + mlp) + a.d * a.vocab


def train_flops_per_token(a: Arch, seq: int) -> float:
    """Forward and backward model FLOPs of one training token.

    6 per matmul parameter, plus causal attention: the scores and the
    weighted sum take 4 * heads * head_dim FLOPs forward per key, a query
    at position i sees i + 1 keys (mean (seq + 1) / 2), and backward is
    twice forward. Recomputation does not count.
    """
    attn = 3 * 4 * a.heads * a.hd * (seq + 1) / 2 * a.layers
    return 6.0 * matmul_params(a) + attn


def round_kernel_bytes(row: int, deg: int, theta_bytes: int,
                       wire_row_bytes: int) -> int:
    """HBM bytes one fused round must move per node row of ``row``
    elements: read theta (``theta_bytes`` an element), lam and the
    previous mean (f32) and ``deg`` neighbour rows as the wire carries
    them (``wire_row_bytes`` each, in-band scales included); write theta,
    lam and the new mean. Counted at the logical row width, not the
    padded tile layout."""
    own = row * (theta_bytes + 4 + 4)
    return 2 * own + deg * wire_row_bytes
