"""Trace spans for the local step and the consensus round.

``span(name)`` is a ``jax.named_scope``: the name lands in the op metadata
(``op_name``) of every HLO instruction traced under it, so a profiler
trace groups the compiled ops under readable phases. It is metadata only,
with no run-time cost, and is always compiled in.

Span naming convention (documented in ``docs/observability.md``, read by
trace viewers as a hierarchy on ``/``). Every op of ``train_step`` lies
under one ``local/*`` phase and every op of the synchronous
``consensus_step`` under one ``consensus/*`` phase:

    local/grad                the loss and its gradient (forward and
                              backward; a backward op's path has
                              ``transpose(``)
    local/optimizer           AdamW: clip, moments, decay, step counter
    consensus/pack            flat-buffer pack + wire encode, and the
                              unpack of the new parameters
    consensus/exchange/off<k> one graph offset's collective-permute+decode
    consensus/probe           objective probes f_i(theta_j)
    consensus/fused_round     the fused Pallas call, its operands and the
                              residual norms
    consensus/penalty         penalty + topology update, round metrics
    wire/encode  wire/decode  codec work inside the phases above
    obs/ring                  metrics-ring appends (obs on only)

The launcher's host spans (``jax.profiler`` annotations on the same
trace) are named in ``launch/train.py``: ``train/step`` with its
``step_num``, ``train/batch``, ``train/dispatch``, ``train/wait`` and
``round/sync`` or ``round/async``.
"""
from __future__ import annotations

import jax


def span(name: str):
    """Named scope for traced code; nests under the active scope."""
    return jax.named_scope(name)
