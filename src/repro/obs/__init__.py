"""Observability subsystem: metrics rings, health monitor, journal, dashboard.

Pieces, one per telemetry concern (details in each module and in
``docs/observability.md``):

  * ``obs.schema``    — THE unified metrics schemas: the per-round key
    set + stable ring-column registry (``ROUND_METRICS``) and the
    per-node registry (``NODE_METRICS``) every round path emits against.
  * ``obs.ring``      — on-device ``[cap, n_metrics]`` scalar metrics
    ring riding in ``TrainState``; appended in-jit, drained every K
    rounds.
  * ``obs.node_ring`` — the per-node ``[cap, J, n_cols]`` telemetry ring
    next to it: per-node residuals, objective, penalty row means,
    staleness ages, liveness and wire bytes.
  * ``obs.trace``     — ``span``: the ``jax.named_scope`` with the
    local-step and round-phase naming convention, always compiled in.
  * ``obs.journal``   — host-side JSONL event journal derived by diffing
    drained ``TopologyState``/``PenaltyState`` snapshots (plus raw
    ``emit`` for health events).
  * ``obs.health``    — online detector bank over drained node rows:
    divergence, eta stall/oscillation, straggler, consensus drift;
    per-node scores and advisory recommendations.
  * ``obs.export``    — the per-run artifact writer (``--obs-dir``):
    metrics/node-metrics/events JSONL, summary rollup, RoundClock
    Perfetto trace, and the artifact validator CLI.
  * ``obs.dashboard`` — renders one obs directory into a single
    self-contained HTML dashboard (``python -m repro.obs.dashboard``).

The rings are off by default and add nothing to the compiled step when
off: ``ConsensusConfig.obs=None`` and ``ObsConfig(enabled=False)`` lower
byte-identical HLO with no ring in it (pinned in ``tests/test_obs.py``);
the ``obs.trace`` scopes are op metadata, always compiled in.
``ObsConfig(with_node_ring=False)`` compiles the
node ring out while keeping the scalar ring.
"""
from repro.obs.export import (ObsWriter, build_rollup,
                              roundclock_trace_events, validate_obs_dir,
                              write_roundclock_trace)
from repro.obs.health import (HEALTH_EVENTS, HealthConfig, HealthMonitor,
                              analyze_trace)
from repro.obs.journal import EventJournal, diff_events, snapshot
from repro.obs.node_ring import (NodeRing, drain_node_rows, init_node_ring,
                                 node_ring_append)
from repro.obs.ring import (MetricsRing, ObsConfig, drain, drain_rows,
                            init_ring, ring_append)
from repro.obs.schema import (COLUMN_INDEX, NODE_COLUMN_INDEX, NODE_COLUMNS,
                              NODE_METRICS, NUM_COLUMNS, NUM_NODE_COLUMNS,
                              RING_COLUMNS, ROUND_METRICS, SCHEMA_VERSION,
                              decode_step, encode_step, metrics_row,
                              node_row, node_row_to_dict, row_to_dict,
                              unify_node_metrics, unify_round_metrics)
from repro.obs.trace import span

__all__ = [
    "COLUMN_INDEX", "EventJournal", "HEALTH_EVENTS", "HealthConfig",
    "HealthMonitor", "MetricsRing", "NODE_COLUMNS", "NODE_COLUMN_INDEX",
    "NODE_METRICS", "NUM_COLUMNS", "NUM_NODE_COLUMNS", "NodeRing",
    "ObsConfig", "ObsWriter", "RING_COLUMNS", "ROUND_METRICS",
    "SCHEMA_VERSION", "analyze_trace", "build_rollup", "decode_step",
    "diff_events", "drain", "drain_node_rows", "drain_rows", "encode_step",
    "init_node_ring", "init_ring",
    "metrics_row", "node_ring_append", "node_row", "node_row_to_dict",
    "ring_append", "roundclock_trace_events", "row_to_dict", "snapshot",
    "span", "unify_node_metrics", "unify_round_metrics",
    "validate_obs_dir", "write_roundclock_trace",
]
