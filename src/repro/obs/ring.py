"""On-device metrics ring: per-round telemetry with zero per-round host syncs.

The consensus step already computes its metrics on device; what used to
make telemetry expensive was the per-round device->host pull (a sync point
that serializes the round pipeline). The ring removes it: a fixed-capacity
``[cap, NUM_COLUMNS]`` f32 buffer rides in ``TrainState`` and each round
appends its ``obs.schema.metrics_row`` in-jit via one
``dynamic_update_slice`` — O(NUM_COLUMNS) bytes of HBM traffic per round,
within noise of the fused round itself (gated <= 3% by ``BENCH_obs.json``).
The host drains the buffer only every K rounds (``ObsConfig.drain_every``),
so steady-state training never blocks on telemetry.

Buffer discipline:

  * ``head`` counts appends MONOTONICALLY; the write slot is
    ``head % cap``. The drain path never writes the device state back —
    the host keeps its own cursor (the last drained head) and reads the
    rows in ``[cursor, head)``, so draining is a pure read and composes
    with state donation (the ring is donated with the rest of the
    TrainState; the drain reads the LIVE output buffers between steps).
  * overflow is explicit, not silent: if more than ``cap`` rounds ran
    since the last drain, the oldest rows were overwritten and ``drain``
    reports how many were dropped (the exporters surface it in the
    rollup). Size ``cap >= drain_every`` to never drop.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import schema


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Knobs for the observability subsystem (``ConsensusConfig.obs``).

    Attributes:
      enabled: master switch. ``ObsConfig(enabled=False)`` is pinned to
        lower BYTE-IDENTICAL HLO to ``obs=None`` — no ring in the
        compiled step when off (tests/test_obs.py).
      ring_capacity: rows in the on-device metrics ring. Must be >=
        ``drain_every`` or steady-state drains drop rows (allowed but
        reported).
      drain_every: host drain cadence in CONSENSUS ROUNDS (the K of the
        amortized-drain accounting in ``launch.dryrun``).
      with_node_ring: carry the per-node telemetry ring
        (``obs.node_ring``: ``[cap, J, NODE_COLUMNS]``) next to the
        scalar ring — per-node residuals, objective, penalty row means,
        staleness ages, liveness and wire bytes, the inputs the health
        monitor (``obs.health``) and the dashboard's per-node heatmaps
        read. Shares ``ring_capacity``/``drain_every``. False keeps the
        scalar-ring-only PR 7 footprint.
    """

    enabled: bool = True
    ring_capacity: int = 256
    drain_every: int = 8
    with_node_ring: bool = True

    def __post_init__(self):
        if self.ring_capacity < 1:
            raise ValueError(f"ring_capacity {self.ring_capacity} < 1")
        if self.drain_every < 1:
            raise ValueError(f"drain_every {self.drain_every} < 1")


class MetricsRing(NamedTuple):
    """Traced fixed-capacity metrics buffer (rides in ``TrainState``)."""

    buf: jax.Array    # [cap, schema.NUM_COLUMNS] f32 — rows, slot = k % cap
    head: jax.Array   # [] int32 — MONOTONIC append count (next write id)


def init_ring(capacity: int) -> MetricsRing:
    return MetricsRing(
        buf=jnp.zeros((int(capacity), schema.NUM_COLUMNS), jnp.float32),
        head=jnp.zeros((), jnp.int32))


def ring_append(ring: MetricsRing, row: jax.Array) -> MetricsRing:
    """Append one ``[NUM_COLUMNS]`` row in-jit (one dynamic_update_slice)."""
    cap = ring.buf.shape[0]
    slot = jax.lax.rem(ring.head, jnp.int32(cap))
    buf = jax.lax.dynamic_update_slice(ring.buf, row[None, :].astype(
        ring.buf.dtype), (slot, jnp.int32(0)))
    return MetricsRing(buf=buf, head=ring.head + 1)


def drain(ring: MetricsRing, cursor: int
          ) -> tuple[np.ndarray, int, int]:
    """Host-side read of every row appended since ``cursor``.

    Returns ``(rows, new_cursor, dropped)`` with ``rows`` a
    ``[n, NUM_COLUMNS]`` numpy array in CHRONOLOGICAL order, ``new_cursor``
    the head to pass next time, and ``dropped`` the count of rows
    overwritten before this drain could read them (0 unless more than
    ``cap`` rounds ran since the last drain). Pure read: the device state
    is never written back, so the caller's jitted steps keep donating the
    ring buffer.
    """
    head = int(ring.head)
    cap = int(ring.buf.shape[0])
    n_new = head - cursor
    if n_new <= 0:
        return np.zeros((0, schema.NUM_COLUMNS), np.float32), head, 0
    dropped = max(0, n_new - cap)
    take = n_new - dropped
    buf = np.asarray(ring.buf)
    idx = (np.arange(head - take, head)) % cap
    return buf[idx], head, dropped


def drain_rows(ring: MetricsRing, cursor: int
               ) -> tuple[list[dict], int, int]:
    """``drain`` + per-row dict conversion (``obs.schema.row_to_dict``)."""
    rows, new_cursor, dropped = drain(ring, cursor)
    return [schema.row_to_dict(r) for r in rows], new_cursor, dropped
