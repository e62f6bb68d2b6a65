"""Tests for the observability subsystem (repro.obs).

Four layers:
  * schema layer — the unified round- and node-metrics registries are
    STABILITY pins: column order is append-only, extra keys are rejected,
    zero is the defined not-applicable value for async-only metrics on
    the sync path, and step cells are int32-bitcast (exact above 2^24 —
    the SCHEMA_VERSION 2 regression pin);
  * host layer — scalar- and node-ring wraparound/drain semantics (pure
    read, cursor, cumulative overflow accounting across multiple wraps),
    topology event journal diffing on synthetic snapshots, the health
    detector bank on synthetic traces (each detector fires exactly where
    the trace was constructed to trip it), exporter artifact
    well-formedness + drain wall-clock timing, RoundClock -> Perfetto
    reconstruction;
  * dashboard layer — render an obs dir to one self-contained HTML and
    self-check every manifest-promised series is present;
  * engine pins (subprocess, 8 fake devices) —
      - sync, async and sharded rounds emit the IDENTICAL metrics key set
        (the metrics-shape-drift satellite pin),
      - both rings append under jit+donation with steps stamped, on the
        sharded and async engines too, and the sharded engine's node
        residuals match the replicated engine's (post-psum values),
      - ``obs=None`` and ``ObsConfig(enabled=False)`` lower BYTE-IDENTICAL
        HLO (zero compiled-step footprint when off — the acceptance pin),
        and ``with_node_ring=False`` compiles the node ring out,
      - the rings exist in TrainState only when their gate is on.
"""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from repro.obs import (ObsConfig, diff_events, drain, drain_rows, init_ring,
                       ring_append, snapshot)
from repro.obs import schema

from script_result import run_result


# -------------------------------------------------------- schema layer ----
def test_schema_column_order_is_pinned():
    """Ring columns are a wire format: existing columns NEVER renumber.

    Appending a new metric is fine (add it to the end of ROUND_METRICS and
    extend this pin); reordering or renaming breaks every drained artifact
    on disk and requires a SCHEMA_VERSION bump instead.
    """
    assert schema.RING_COLUMNS == (
        "step", "r_max", "s_max", "f_mean", "eta_mean", "active_edges",
        "stale_edges", "age_max")
    assert schema.NUM_COLUMNS == 8
    assert schema.COLUMN_INDEX["step"] == 0
    assert schema.COLUMN_INDEX["age_max"] == 7
    # v2: step cells became int32-bitcast + the NODE_COLUMNS registry landed
    assert schema.SCHEMA_VERSION == 2
    assert schema.NODE_COLUMNS == (
        "step", "r", "s", "f_local", "eta_row_mean", "age_max", "alive",
        "advance", "wire_rx_bytes")
    assert schema.NUM_NODE_COLUMNS == 9
    assert schema.NODE_COLUMN_INDEX["step"] == 0
    assert schema.NODE_COLUMN_INDEX["wire_rx_bytes"] == 8


def test_unify_pads_missing_and_rejects_unregistered():
    out = schema.unify_round_metrics({"r_max": 1.0, "s_max": 2.0})
    assert tuple(out) == schema.ROUND_METRICS       # registry order
    assert float(out["stale_edges"]) == 0.0
    assert out["age_max"].dtype == np.int32         # typed zero
    with pytest.raises(ValueError, match="unregistered"):
        schema.unify_round_metrics({"r_max": 1.0, "my_new_metric": 3.0})


def test_metrics_row_roundtrips_through_row_to_dict():
    row = schema.metrics_row(7, {"r_max": 0.5, "age_max": 3})
    assert row.shape == (schema.NUM_COLUMNS,)
    d = schema.row_to_dict(np.asarray(row))
    assert d["step"] == 7 and isinstance(d["step"], int)
    assert d["age_max"] == 3 and isinstance(d["age_max"], int)
    assert d["r_max"] == pytest.approx(0.5)
    assert d["s_max"] == 0.0


def test_obs_config_validation():
    with pytest.raises(ValueError):
        ObsConfig(ring_capacity=0)
    with pytest.raises(ValueError):
        ObsConfig(drain_every=0)
    assert ObsConfig().enabled is True


# ---------------------------------------------------------- ring layer ----
def _rows(n, start=0):
    return [schema.metrics_row(start + k, {"r_max": float(start + k)})
            for k in range(n)]


def _steps(raw_rows):
    """Step ids out of raw drained rows (the cell is an int32 bitcast)."""
    return [schema.decode_step(r[schema.COLUMN_INDEX["step"]])
            for r in raw_rows]


def test_ring_drain_is_chronological_and_pure():
    ring = init_ring(8)
    for row in _rows(3):
        ring = ring_append(ring, row)
    rows, cursor, dropped = drain(ring, 0)
    assert dropped == 0 and cursor == 3
    assert _steps(rows) == [0, 1, 2]
    # pure read: same cursor -> same rows, device state untouched
    rows2, _, _ = drain(ring, 0)
    assert np.array_equal(rows, rows2)
    assert int(ring.head) == 3
    # cursor honored: nothing new since
    rows3, cursor3, _ = drain(ring, cursor)
    assert rows3.shape[0] == 0 and cursor3 == 3


def test_ring_wraparound_reports_dropped_rows():
    ring = init_ring(4)
    for row in _rows(7):                 # 7 appends into cap 4
        ring = ring_append(ring, row)
    rows, cursor, dropped = drain(ring, 0)
    assert dropped == 3                  # rows 0,1,2 overwritten
    assert cursor == 7
    # survivors are the newest cap rows, still chronological
    assert _steps(rows) == [3, 4, 5, 6]


def test_ring_append_wraps_under_jit():
    import jax

    @jax.jit
    def appends(ring):
        for row in _rows(5):
            ring = ring_append(ring, row)
        return ring

    ring = appends(init_ring(4))
    assert int(ring.head) == 5
    rows, _, dropped = drain(ring, 0)
    assert dropped == 1
    assert _steps(rows) == [1, 2, 3, 4]


def test_drain_rows_dict_form():
    ring = init_ring(4)
    ring = ring_append(ring, schema.metrics_row(9, {"age_max": 2}))
    rows, cursor, _ = drain_rows(ring, 0)
    assert cursor == 1
    assert rows[0]["step"] == 9 and rows[0]["age_max"] == 2
    assert set(rows[0]) == set(schema.RING_COLUMNS)


def test_step_stamp_exact_past_f32_significand():
    """The satellite regression pin: steps above 2^24 survive the ring.

    f32 has a 24-bit significand, so storing the step as a float VALUE
    rounds 16_777_217 to 16_777_216 (and every odd id above it to an even
    neighbor). The int32-bitcast cell (SCHEMA_VERSION 2) carries all 32
    bits exactly.
    """
    big = 16_777_216                      # 2^24: the f32 precision cliff
    steps = [big - 1, big, big + 1, big + 3]
    # the float-value encoding demonstrably cannot represent these
    assert int(np.float32(big + 1)) != big + 1
    ring = init_ring(8)
    for s in steps:
        ring = ring_append(ring, schema.metrics_row(s, {"r_max": 1.0}))
    rows, _, dropped = drain_rows(ring, 0)
    assert dropped == 0
    assert [r["step"] for r in rows] == steps
    # and the raw-cell path decodes identically
    raw, _, _ = drain(ring, 0)
    assert _steps(raw) == steps


def test_multi_wrap_drain_accumulates_dropped():
    """Drain cadence slower than the ring: rows overwritten BETWEEN drains
    are counted, cumulatively, and survivors stay chronological across
    several full wraps (drain_every > ring_capacity misconfigurations
    degrade to sampled telemetry, never to silent corruption)."""
    cap = 4
    ring = init_ring(cap)
    cursor, total_dropped, seen = 0, 0, []
    k = 0
    for burst in (6, 9, 4, 13):           # every burst > cap wraps fully
        for _ in range(burst):
            ring = ring_append(ring, schema.metrics_row(
                k, {"r_max": float(k)}))
            k += 1
        rows, cursor, dropped = drain(ring, cursor)
        total_dropped += dropped
        assert dropped == burst - cap     # the overwritten prefix, per gap
        got = _steps(rows)
        assert got == sorted(got) and len(got) == cap
        assert got[-1] == k - 1           # newest survivor is last append
        seen += got
    assert int(ring.head) == k == sum((6, 9, 4, 13))
    assert total_dropped == k - len(seen)
    assert seen == sorted(seen)           # chronological ACROSS drains too


# ----------------------------------------------------- node ring layer ----
def _slab(step, j=3, **metrics):
    return schema.node_row(step, metrics, j)


def test_node_ring_append_drain_and_dict_form():
    from repro.obs import drain_node_rows, init_node_ring, node_ring_append
    ring = init_node_ring(4, num_nodes=3)
    ring = node_ring_append(ring, _slab(
        7, r=np.array([0.1, 0.2, 0.3]), age_max=np.array([0, 2, 1]),
        alive=np.array([1.0, 1.0, 0.0])))
    ring = node_ring_append(ring, _slab(8, r=np.array([0.4, 0.5, 0.6])))
    rows, cursor, dropped = drain_node_rows(ring, 0)
    assert cursor == 2 and dropped == 0
    assert [r["step"] for r in rows] == [7, 8]
    assert set(rows[0]) == set(schema.NODE_COLUMNS)
    assert rows[0]["r"] == pytest.approx([0.1, 0.2, 0.3])
    assert rows[0]["age_max"] == [0, 2, 1]
    assert all(isinstance(v, int) for v in rows[0]["age_max"])
    assert rows[0]["alive"] == [1.0, 1.0, 0.0]
    # unreported flags pad to "everyone live and advancing" (sync path)
    assert rows[1]["alive"] == [1.0, 1.0, 1.0]
    assert rows[1]["advance"] == [1.0, 1.0, 1.0]
    assert rows[1]["s"] == [0.0, 0.0, 0.0]
    # pure read: drain again from the same cursor, same rows
    rows2, _, _ = drain_node_rows(ring, 0)
    assert rows2 == rows


def test_node_ring_wraparound_and_cursor():
    from repro.obs import drain_node_rows, init_node_ring, node_ring_append
    ring = init_node_ring(2, num_nodes=2)
    for s in range(5):
        ring = node_ring_append(ring, _slab(s, j=2,
                                            r=np.full(2, float(s))))
    rows, cursor, dropped = drain_node_rows(ring, 0)
    assert dropped == 3 and cursor == 5
    assert [r["step"] for r in rows] == [3, 4]
    assert rows[-1]["r"] == [4.0, 4.0]
    # cursor honored
    rows2, cursor2, dropped2 = drain_node_rows(ring, cursor)
    assert rows2 == [] and cursor2 == 5 and dropped2 == 0


def test_node_ring_append_under_jit():
    import jax
    from repro.obs import drain_node_rows, init_node_ring, node_ring_append

    @jax.jit
    def appends(ring):
        for s in range(3):
            ring = node_ring_append(ring, _slab(s, j=2))
        return ring

    rows, _, dropped = drain_node_rows(appends(init_node_ring(4, 2)), 0)
    assert dropped == 0 and [r["step"] for r in rows] == [0, 1, 2]


def test_unify_node_metrics_pads_and_rejects():
    out = schema.unify_node_metrics({"r": np.array([1.0, 2.0])}, 2)
    assert tuple(out) == schema.NODE_METRICS
    assert np.asarray(out["alive"]).tolist() == [1.0, 1.0]
    assert np.asarray(out["advance"]).tolist() == [1.0, 1.0]
    assert np.asarray(out["age_max"]).dtype == np.int32
    assert np.asarray(out["wire_rx_bytes"]).tolist() == [0.0, 0.0]
    with pytest.raises(ValueError, match="unregistered"):
        schema.unify_node_metrics({"r": np.zeros(2), "nope": np.zeros(2)}, 2)


# ---------------------------------------------------------- health layer ----
def _trace(j, n, r=None, eta=None, age=None, alive=None, start=0):
    """Synthetic node-row trace: per-metric callables of (step, node).

    The defaults are a CLEAN node: flat residual on the fleet median and a
    slowly drifting eta (a frozen default would trip the stall detector in
    every test) — so each test constructs exactly one anomaly.
    """
    rows = []
    for t in range(n):
        step = start + t
        rows.append({
            "step": step,
            "r": [r(t, i) if r else 1e-3 for i in range(j)],
            "s": [0.0] * j,
            "f_local": [1.0] * j,
            "eta_row_mean": [eta(t, i) if eta else 0.1 + 0.01 * (start + t)
                             for i in range(j)],
            "age_max": [age(t, i) if age else 0 for i in range(j)],
            "alive": [alive(t, i) if alive else 1.0 for i in range(j)],
            "advance": [1.0] * j,
            "wire_rx_bytes": [256.0] * j,
        })
    return rows


def test_health_divergence_fires_once_on_the_growing_node():
    from repro.obs import HealthConfig, HealthMonitor
    mon = HealthMonitor(4, HealthConfig(window=8))
    # node 2's residual doubles every round; everyone else holds flat.
    # eta drifts so the frozen-eta detector has nothing to say.
    ev = mon.observe_rows(_trace(
        4, 12,
        r=lambda t, i: 1e-3 * (2.0 ** t) if i == 2 else 1e-3,
        eta=lambda t, i: 0.1 + 0.01 * t))
    div = [e for e in ev if e["event"] == "health_divergence"]
    assert len(div) == 1                 # edge-triggered: one per episode
    assert div[0]["node"] == 2
    assert div[0]["r_late"] > 2.0 * div[0]["r_early"]
    # drift fires for node 2 as well (it IS far off the fleet median);
    # no other node trips any detector
    assert all(e["node"] == 2 for e in ev)
    assert mon.scores()[2] < mon.scores()[0] == 1.0


def test_health_eta_stall_and_oscillation_are_disjoint():
    from repro.obs import HealthConfig, HealthMonitor
    mon = HealthMonitor(4, HealthConfig(window=8))
    # node 1: eta frozen while its residual is material  -> stall
    #   (3e-3 is material vs min_residual yet under drift_ratio x median,
    #    so the stall is the ONLY thing node 1 trips)
    # node 3: eta flaps +-0.05 every round               -> oscillation
    # nodes 0/2: eta drifts monotonically, tiny residual -> clean
    ev = mon.observe_rows(_trace(
        4, 10,
        r=lambda t, i: 3e-3 if i == 1 else 1e-3,
        eta=lambda t, i: (0.1 if i == 1 else
                          0.1 + 0.05 * (t % 2) if i == 3 else
                          0.1 + 0.01 * t)))
    kinds = {}
    for e in ev:
        kinds.setdefault(e["event"], []).append(e["node"])
    assert kinds["health_eta_stall"] == [1]
    assert kinds["health_eta_oscillation"] == [3]
    assert set(kinds) == {"health_eta_stall", "health_eta_oscillation"}
    rec = mon.recommendations()
    assert rec["budget_topup"] == [1]    # stalled eta -> eq. (10) top-up
    assert any("eq. 10" in n for n in rec["notes"])


def test_health_straggler_age_and_lag_paths():
    from repro.obs import HealthConfig, HealthMonitor
    mon = HealthMonitor(4, HealthConfig(window=8), max_staleness=4)
    ev = mon.observe_rows(_trace(
        4, 8, age=lambda t, i: 3 if i == 2 else 0))
    strag = [e for e in ev if e["event"] == "health_straggler"]
    assert [e["node"] for e in strag] == [2]
    assert strag[0]["mean_age"] == pytest.approx(3.0)
    # the clock-lag path (executor summary) is independent of ages
    ev2 = mon.observe_executor({"round_lag": [0, 0, 0, 5]})
    assert [e["node"] for e in ev2] == [3]
    assert ev2[0]["lag"] == 5
    tab = mon.table()
    assert tab["nodes"][3]["lag"] == 5
    assert tab["nodes"][2]["straggler"] and tab["nodes"][3]["straggler"]


def test_health_drift_needs_no_growth_and_rearms():
    from repro.obs import HealthConfig, HealthMonitor
    mon = HealthMonitor(4, HealthConfig(window=4))
    # node 0 sits at 0.5 while the fleet median is 1e-3: drift, not
    # divergence (its residual never grows)
    ev = mon.observe_rows(_trace(
        4, 6, r=lambda t, i: 0.5 if i == 0 else 1e-3))
    assert [e["event"] for e in ev] == ["health_drift"]
    assert ev[0]["node"] == 0
    # recovery clears the verdict...
    assert mon.observe_rows(_trace(4, 6, start=6)) == []
    assert mon.scores() == [1.0] * 4
    # ...and a relapse is a NEW episode (the edge re-arms). The jump back
    # up legitimately looks like divergence too for a few rows; only the
    # drift fire COUNT is the re-arm pin.
    ev3 = mon.observe_rows(_trace(
        4, 6, r=lambda t, i: 0.5 if i == 0 else 1e-3, start=12))
    assert "health_drift" in {e["event"] for e in ev3}
    assert all(e["node"] == 0 for e in ev3)
    assert mon.table()["nodes"][0]["fires"]["drift"] == 2


def test_health_dead_nodes_render_no_verdicts():
    from repro.obs import HealthConfig, HealthMonitor
    mon = HealthMonitor(3, HealthConfig(window=4))
    # node 1 is a ghost row carrying a huge stale residual: no events, and
    # the fleet median is taken over LIVE nodes only
    ev = mon.observe_rows(_trace(
        3, 6, r=lambda t, i: 9.9 if i == 1 else 1e-3,
        alive=lambda t, i: 0.0 if i == 1 else 1.0))
    assert ev == []
    assert mon.scores() == [1.0, 1.0, 1.0]


def test_health_events_ride_the_journal_and_analyze_trace(tmp_path):
    from repro.obs import EventJournal, HealthConfig, analyze_trace
    path = str(tmp_path / "events.jsonl")
    rows = _trace(4, 8, r=lambda t, i: 3e-3 if i == 1 else 1e-3,
                  eta=lambda t, i: 0.1 if i == 1 else 0.1 + 0.01 * t)
    with EventJournal(path) as j:
        res = analyze_trace(rows, 4, cfg=HealthConfig(window=8), journal=j,
                            executor_summary={"round_lag": [0, 6, 0, 0]})
    lines = [json.loads(ln) for ln in open(path)]
    assert lines == res["events"]
    kinds = sorted(e["event"] for e in lines)
    assert kinds == ["health_eta_stall", "health_straggler"]
    assert all(e["node"] == 1 for e in lines)
    # score: 1 - 0.2 (stall) - 0.3 (straggler) = 0.5 -> not a drop
    # candidate (drop needs score < 0.5 AND a hard detector)
    assert res["table"]["nodes"][1]["score"] == pytest.approx(0.5)
    assert res["recommendations"]["drop_candidates"] == []
    assert res["recommendations"]["budget_topup"] == [1]


# ------------------------------------------------------- journal layer ----
def _topo(j=4, **kw):
    base = dict(mask=np.ones((j, j), bool), node_alive=np.ones(j, bool),
                repair=np.zeros((j, j), bool), age=np.zeros((j, j), np.int32),
                kick=np.zeros((j, j), np.float32))
    base.update(kw)
    return SimpleNamespace(**base)


def _pen(j=4, **kw):
    base = dict(eta=np.full((j, j), 0.1, np.float32),
                cum_tau=np.zeros((j, j), np.float32),
                budget=np.ones((j, j), np.float32),
                n_incr=np.zeros((j, j), np.int32))
    base.update(kw)
    return SimpleNamespace(**base)


def test_journal_diff_gate_revive_and_churn():
    prev = snapshot(_topo(), _pen())
    mask = np.ones((4, 4), bool)
    mask[0, 1] = mask[1, 0] = False      # symmetric gate
    mask[2, 3] = False                   # one-sided flip gates too: an edge
                                         # is active iff BOTH directions are
    alive = np.ones(4, bool)
    alive[3] = False
    repair = np.zeros((4, 4), bool)
    repair[1, 2] = True
    cur = snapshot(_topo(mask=mask, node_alive=alive, repair=repair), _pen())
    ev = diff_events(prev, cur, step=5)
    by = {}
    for e in ev:
        by.setdefault(e["event"], []).append(e)
    assert [e["edge"] for e in by["edge_gated"]] == [[0, 1], [2, 3]]
    assert by["edge_gated"][0]["step"] == 5
    assert by["node_dropped"][0]["node"] == 3
    assert by["repair_activated"][0]["edge"] == [1, 2]
    assert "edge_revived" not in by
    # revive is the reverse diff
    ev_back = diff_events(cur, prev, step=6)
    assert any(e["event"] == "edge_revived" and e["edge"] == [0, 1]
               for e in ev_back)


def test_journal_diff_staleness_and_kick():
    prev = snapshot(_topo(), _pen())
    age = np.zeros((4, 4), np.int32)
    age[1, 2] = 3                        # symmetrized: max(age, age.T)
    kick = np.zeros((4, 4), np.float32)
    kick[0, 3] = kick[3, 0] = 0.5
    cur = snapshot(_topo(age=age, kick=kick), _pen())
    ev = diff_events(prev, cur, step=2, max_staleness=1)
    kinds = {e["event"]: e for e in ev}
    assert kinds["stale_gated"]["edge"] == [1, 2]
    assert kinds["stale_gated"]["age"] == 3
    assert kinds["kick_parked"]["edge"] == [0, 3]
    assert kinds["kick_parked"]["weight"] == pytest.approx(0.5)
    ev_back = diff_events(cur, prev, step=3, max_staleness=1)
    kinds = {e["event"]: e for e in ev_back}
    assert kinds["stale_revived"]["edge"] == [1, 2]
    assert kinds["kick_absorbed"]["weight"] == pytest.approx(0.5)
    # without the bound there are no staleness events (executor config)
    assert not any("stale" in e["event"]
                   for e in diff_events(prev, cur, step=2))


def test_journal_diff_budget_lifecycle_is_directed():
    prev = snapshot(_topo(), _pen())
    tau = np.zeros((4, 4), np.float32)
    tau[0, 1] = 2.0                      # exhausted one direction only
    n_incr = np.zeros((4, 4), np.int32)
    n_incr[2, 0] = 1
    cur = snapshot(_topo(), _pen(cum_tau=tau, n_incr=n_incr,
                                 budget=np.full((4, 4), 1.5, np.float32)))
    ev = diff_events(prev, cur, step=9)
    kinds = {e["event"]: e for e in ev}
    assert kinds["budget_exhausted"]["edge"] == [0, 1]
    assert kinds["budget_exhausted"]["cum_tau"] == pytest.approx(2.0)
    assert kinds["budget_topup"]["edge"] == [2, 0]
    assert kinds["budget_topup"]["n_incr"] == 1
    assert sum(e["event"] == "budget_exhausted" for e in ev) == 1


def test_event_journal_baseline_and_jsonl(tmp_path):
    from repro.obs import EventJournal
    path = str(tmp_path / "events.jsonl")
    with EventJournal(path, max_staleness=1) as j:
        assert j.observe(_topo(), _pen(), step=0) == []   # baseline
        mask = np.ones((4, 4), bool)
        mask[0, 2] = mask[2, 0] = False
        ev = j.observe(_topo(mask=mask), _pen(), step=4)
        assert len(ev) == 1
        assert j.observe(_topo(mask=mask), _pen(), step=8) == []  # no diff
    lines = [json.loads(ln) for ln in open(path)]
    assert lines == [{"step": 4, "event": "edge_gated",
                      "edge": [0, 2], "eta": pytest.approx(0.1)}]


# -------------------------------------------------------- export layer ----
def test_obs_writer_artifact_set(tmp_path):
    from repro.obs import ObsWriter, validate_obs_dir
    d = str(tmp_path / "run")
    w = ObsWriter(d, meta={"wire_codec": "native",
                           "wire_bytes_per_round": 123, "offsets": [1]})
    w.append_metrics([schema.row_to_dict(np.asarray(r)) for r in _rows(3)])
    w.journal.observe(_topo(), _pen(), step=0)
    rollup = w.finalize(extra={"note": "test"})
    assert rollup["rounds"] == 3
    assert rollup["convergence"]["r_max"] == [0.0, 1.0, 2.0]
    assert rollup["wire"]["wire_bytes_per_round"] == 123
    assert rollup["note"] == "test"
    report = validate_obs_dir(d)
    assert report["ok"], report["errors"]
    assert report["files"]["metrics.jsonl"]["rows"] == 3
    # clock trace is optional, its absence is reported but not failed
    assert report["files"]["roundclock_trace.json"]["present"] is False


def _spool_run(d, *, j=3, rounds=6, drain_every=3, health=False,
               max_staleness=None):
    """Drive an ObsWriter through both rings like a launcher would."""
    import jax.numpy as jnp
    from repro.obs import (ObsWriter, init_node_ring, init_ring,
                           node_ring_append, ring_append)
    w = ObsWriter(d, meta={"wire_codec": "native",
                           "wire_bytes_per_round": 64, "offsets": [1]},
                  health=health, max_staleness=max_staleness)
    state = SimpleNamespace(ring=init_ring(8),
                            node_ring=init_node_ring(8, num_nodes=j),
                            topo=_topo(j), penalty=_pen(j))
    for s in range(rounds):
        state.ring = ring_append(state.ring, schema.metrics_row(
            s, {"r_max": 0.1 / (s + 1), "s_max": 0.05, "f_mean": 1.0,
                "eta_mean": 0.1}))
        state.node_ring = node_ring_append(state.node_ring, schema.node_row(
            s, {"r": jnp.full((j,), 0.1 / (s + 1)),
                "eta_row_mean": jnp.full((j,), 0.1),
                "wire_rx_bytes": jnp.full((j,), 64.0)}, j))
        if (s + 1) % drain_every == 0:
            w.drain(state, step=s)
    w.drain(state, step=rounds)
    return w


def test_obs_writer_spools_node_metrics_timing_and_health(tmp_path):
    from repro.obs import validate_obs_dir
    d = str(tmp_path / "run")
    w = _spool_run(d, health=True)
    w.observe_executor({"rounds_done": [6, 6, 5], "round_lag": [0, 0, 1],
                        "lag_p50": 0, "lag_p90": 1, "lag_p100": 1})
    rollup = w.finalize()
    assert rollup["rounds"] == 6
    # satellite pin: host wall-clock per drain -> rollup round_ms. The
    # first drain only anchors the clock; the second covers 3 rounds.
    t = rollup["timing"]
    assert t["drains"] == 1 and t["round_ms"] >= 0.0
    assert set(t) >= {"drains", "round_ms", "round_ms_p50", "round_ms_max"}
    pn = rollup["per_node"]
    assert pn["num_nodes"] == 3 and pn["rounds"] == 6
    assert pn["dropped_rows"] == 0
    assert pn["wire_rx_bytes_total"] == pytest.approx([6 * 64.0] * 3)
    # health table + advisory block land in the rollup when --health is on
    assert rollup["health"]["rows_seen"] == 6
    assert len(rollup["health"]["nodes"]) == 3
    assert "recommendations" in rollup["health"]
    assert rollup["executor"]["lag_p100"] == 1
    report = validate_obs_dir(d)
    assert report["ok"], report["errors"]
    assert report["files"]["node_metrics.jsonl"]["rows"] == 6
    rows = [json.loads(ln) for ln in open(os.path.join(
        d, "node_metrics.jsonl"))]
    assert set(rows[0]) == set(schema.NODE_COLUMNS)
    assert rows[0]["step"] == 0 and len(rows[0]["r"]) == 3


def test_obs_writer_without_node_ring_stays_valid(tmp_path):
    """A scalar-only run (with_node_ring=False) writes no node artifacts
    and the validator treats their absence as fine, not as an error."""
    from repro.obs import ObsWriter, init_ring, ring_append, validate_obs_dir
    d = str(tmp_path / "run")
    w = ObsWriter(d, meta={"wire_codec": "native",
                           "wire_bytes_per_round": 64, "offsets": [1]})
    state = SimpleNamespace(ring=init_ring(8), node_ring=None,
                            topo=_topo(), penalty=_pen())
    state.ring = ring_append(state.ring, schema.metrics_row(
        0, {"r_max": 0.1}))
    w.drain(state, step=0)
    rollup = w.finalize()
    assert rollup["per_node"] == {}
    report = validate_obs_dir(d)
    assert report["ok"], report["errors"]
    assert report["files"]["node_metrics.jsonl"]["present"] is False


# ------------------------------------------------------ dashboard layer ----
def test_dashboard_renders_and_self_checks(tmp_path):
    from repro.obs.dashboard import check_dashboard, render_dashboard
    d = str(tmp_path / "run")
    w = _spool_run(d, health=True, max_staleness=4)
    w.journal.emit({"step": 3, "event": "edge_gated", "edge": [0, 1]})
    w.finalize()
    path = render_dashboard(d)
    assert path == os.path.join(d, "dashboard.html")
    report = check_dashboard(path)
    assert report["ok"], report["errors"]
    # the run had node rows, so the per-node heatmaps must be promised
    assert {"residuals", "node_r", "events", "health_table"} <= set(
        report["series"])
    html = open(path).read()
    assert "<svg" in html and "dash-manifest" in html
    # self-contained: nothing in the page references a remote resource
    # (the SVG xmlns namespace URI is an identifier, not a fetch)
    for needle in ('src="http', "src='http", 'href="http', "href='http",
                   "url(http", "@import", "fetch("):
        assert needle not in html, needle


def test_dashboard_check_catches_a_dropped_section(tmp_path):
    from repro.obs.dashboard import check_dashboard, render_dashboard
    d = str(tmp_path / "run")
    _spool_run(d).finalize()
    path = render_dashboard(d)
    html = open(path).read()
    with open(path, "w") as f:                # silently drop one section
        f.write(html.replace('id="series-node_r"', 'id="series-oops"'))
    report = check_dashboard(path)
    assert not report["ok"]
    assert any("node_r" in e for e in report["errors"])


def test_dashboard_cli_roundtrip(tmp_path):
    from repro.obs.dashboard import main
    d = str(tmp_path / "run")
    _spool_run(d).finalize()
    out = str(tmp_path / "dash.html")
    assert main([d, "-o", out, "--check"]) == 0
    assert os.path.exists(out)


def test_validator_fails_on_missing_and_malformed(tmp_path):
    from repro.obs import validate_obs_dir
    d = str(tmp_path / "broken")
    os.makedirs(d)
    report = validate_obs_dir(d)
    assert not report["ok"]
    assert any("metrics.jsonl: missing" in e for e in report["errors"])
    # a metrics row missing schema keys is an error too
    for name in ("run.json", "rollup.json"):
        with open(os.path.join(d, name), "w") as f:
            json.dump({"rounds": 0, "convergence": {}, "staleness": {}}, f)
    with open(os.path.join(d, "events.jsonl"), "w"):
        pass
    with open(os.path.join(d, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"step": 1}) + "\n")
    report = validate_obs_dir(d)
    assert any("missing keys" in e for e in report["errors"])


def test_roundclock_perfetto_reconstruction(tmp_path):
    from repro.async_exec import RoundClock, straggler_compute
    from repro.obs import roundclock_trace_events, write_roundclock_trace
    clock = RoundClock(compute_s=straggler_compute(3, factor=2.0),
                       wire_s=0.25, offsets=(1,))
    for _ in range(4):
        clock.tick()
    ev = roundclock_trace_events(clock)
    spans = [e for e in ev if e["ph"] == "X" and e["cat"] == "compute"]
    wires = [e for e in ev if e["ph"] == "X" and e["cat"] == "wire"]
    ticks = [e for e in ev if e["ph"] == "i"]
    assert len(spans) == int(np.sum(clock.rounds_done))
    assert len(wires) == len(spans)      # every round sends once
    assert len(ticks) == 4
    # straggler node 0 rounds are 2x wide; sends start at round end
    w0 = [e for e in spans if e["tid"] == 0][0]
    w1 = [e for e in spans if e["tid"] == 1][0]
    assert w0["dur"] == pytest.approx(2 * w1["dur"])
    s1 = [e for e in wires if e["tid"] == 3 + 1][0]
    assert s1["ts"] == pytest.approx(w1["ts"] + w1["dur"])
    path = write_roundclock_trace(clock, str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert doc["traceEvents"] and doc["otherData"]["tick_s"] == clock.tick_s


# ----------------------------------------------- engine layer (8 dev) ----
_ENGINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
from repro.async_exec import AsyncConfig, AsyncExecutor
from repro.configs import get_reduced_config
from repro.core.penalty import PenaltyConfig
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.obs import ObsConfig
from repro.obs import node_ring as node_ring_lib
from repro.obs import ring as ring_lib
from repro.obs import schema
from repro.optim import ConsensusConfig, ConsensusTrainer
from repro.optim.adamw import AdamWConfig
from repro.topology import TopologyConfig

out = {}
mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
cfg = get_reduced_config("qwen3-4b")
model = build_model(cfg)
data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  batch_per_node=2, num_nodes=4))
probe = data.batch(0, probe=True)

def make(obs=None, async_cfg=None, sharded=False, pipe=1):
    return ConsensusTrainer(
        model, mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=PenaltyConfig(scheme="nap", eta0=0.1),
            topology="ring", local_steps=1,
            dyn_topology=TopologyConfig(),
            async_exec=async_cfg, shard_consensus=sharded,
            pipeline_offsets=pipe, obs=obs))

# --- 1. obs off leaves ZERO footprint: byte-identical HLO ---------------
hlo = {}
for tag, obs in (("none", None), ("disabled", ObsConfig(enabled=False)),
                 ("scalar_only", ObsConfig(ring_capacity=8,
                                           with_node_ring=False)),
                 ("enabled", ObsConfig(ring_capacity=8))):
    tr = make(obs=obs)
    st = tr.init_state(jax.random.PRNGKey(0))
    hlo[tag] = jax.jit(tr.consensus_step).lower(st, probe).as_text()
    if tag in ("none", "disabled"):
        out[f"ring_is_none_{tag}"] = st.ring is None
    out[f"node_ring_is_none_{tag}"] = st.node_ring is None
out["hlo_off_byte_identical"] = hlo["none"] == hlo["disabled"]
out["hlo_enabled_differs"] = hlo["none"] != hlo["enabled"]
# with_node_ring=False compiles the node ring OUT: the program differs
# from the full telemetry plane but still carries the scalar ring
out["hlo_scalar_only_differs_from_enabled"] = (
    hlo["scalar_only"] != hlo["enabled"])
out["hlo_scalar_only_differs_from_off"] = hlo["scalar_only"] != hlo["none"]
out["hlo_enabled_has_ring_write"] = (
    "dynamic_update_slice" in hlo["enabled"]        # stablehlo spelling
    or "dynamic-update-slice" in hlo["enabled"])    # hlo spelling

# --- 2. ring under the REAL jitted step fns (donation path) -------------
results = {}
for tag, kw in (("sync", {}), ("sharded", {"sharded": True}),
                ("pipelined", {"pipe": 4})):
    tr = make(obs=ObsConfig(ring_capacity=8), **kw)
    st = tr.init_state(jax.random.PRNGKey(0))
    train, cons = tr.jit_step_fns()
    for s in range(3):      # launcher cadence: train step then round, so
        st, m = train(st, data.batch(s))        # the stamped steps differ
        st, m = cons(st, data.batch(s, probe=True))
    rows, cursor, dropped = ring_lib.drain_rows(st.ring, 0)
    out[f"{tag}_ring_rows"] = len(rows)
    out[f"{tag}_ring_dropped"] = dropped
    out[f"{tag}_ring_steps"] = [r["step"] for r in rows]
    out[f"{tag}_keys"] = sorted(m)
    nrows, _, ndropped = node_ring_lib.drain_node_rows(st.node_ring, 0)
    results[tag] = (rows, m, nrows)
    out[f"{tag}_node_rows"] = len(nrows)
    out[f"{tag}_node_dropped"] = ndropped
    out[f"{tag}_node_steps"] = [r["step"] for r in nrows]
    out[f"{tag}_node_keys"] = sorted(nrows[0]) if nrows else []
    out[f"{tag}_node_r"] = [r["r"] for r in nrows]
    out[f"{tag}_node_alive"] = nrows[-1]["alive"] if nrows else []
    out[f"{tag}_node_rx"] = nrows[-1]["wire_rx_bytes"] if nrows else []

# value-consistency pin: the sharded engine's per-node residuals are the
# post-psum replicated values — identical to the replicated engine's up
# to float reassociation
out["node_sync_sharded_r_close"] = bool(np.allclose(
    np.asarray(out["sync_node_r"]), np.asarray(out["sharded_node_r"]),
    rtol=1e-2, atol=1e-3))
# round-pipeline pin: pipelining is a pure reordering, so the node ring's
# telemetry — wire_rx accounting included — is EXACTLY the sequential
# engine's, row for row
out["node_pipelined_rows_equal_sync"] = (
    results["pipelined"][2] == results["sync"][2])
out["ring_pipelined_rows_equal_sync"] = (
    results["pipelined"][0] == results["sync"][0])

# --- 3. async executor rounds append too, same key set ------------------
tra = make(obs=ObsConfig(ring_capacity=8),
           async_cfg=AsyncConfig(max_staleness=1))
sta = tra.init_state(jax.random.PRNGKey(0))
train_a = tra.jit_step_fns()[0]
sta, _ = train_a(sta, data.batch(0))
ex = AsyncExecutor(tra)
for s in range(1, 4):
    sta, ma = ex.consensus_round(sta, probe)
rows_a, _, _ = ring_lib.drain_rows(sta.ring, 0)
out["async_ring_rows"] = len(rows_a)
out["async_keys"] = sorted(ma)
nrows_a, _, _ = node_ring_lib.drain_node_rows(sta.node_ring, 0)
out["async_node_rows"] = len(nrows_a)
out["async_node_keys"] = sorted(nrows_a[0]) if nrows_a else []
out["async_node_alive"] = nrows_a[-1]["alive"] if nrows_a else []
out["async_node_advance"] = nrows_a[-1]["advance"] if nrows_a else []
out["async_node_ages_ok"] = all(
    isinstance(v, int) and 0 <= v <= 1
    for r in nrows_a for v in r["age_max"])
out["schema_keys"] = sorted(schema.ROUND_METRICS)
out["node_schema_keys"] = sorted(schema.NODE_COLUMNS)
out["row_keys_match_schema"] = all(
    set(r) == set(schema.RING_COLUMNS) for r in results["sync"][0] + rows_a)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def engine_results():
    return run_result(_ENGINE, timeout=1800)


def test_obs_off_is_byte_identical_hlo(engine_results):
    """Acceptance pin: with obs unset (or enabled=False) the compiled
    consensus step is BYTE-IDENTICAL to a build that never heard of obs —
    no ring in the state, no spans in the HLO metadata, nothing."""
    assert engine_results["hlo_off_byte_identical"] is True
    assert engine_results["ring_is_none_none"] is True
    assert engine_results["ring_is_none_disabled"] is True


def test_obs_enabled_adds_exactly_the_ring_write(engine_results):
    assert engine_results["hlo_enabled_differs"] is True
    assert engine_results["hlo_enabled_has_ring_write"] is True


def test_ring_appends_under_jit_and_donation(engine_results):
    """The jitted (donating) step fns append one stamped row per round on
    both the replicated and the sharded engine; the pure-read drain sees
    them all."""
    for tag in ("sync", "sharded"):
        assert engine_results[f"{tag}_ring_rows"] == 3
        assert engine_results[f"{tag}_ring_dropped"] == 0
        steps = engine_results[f"{tag}_ring_steps"]
        assert steps == sorted(steps) and len(set(steps)) == 3
    assert engine_results["async_ring_rows"] == 3


def test_metrics_key_set_is_unified(engine_results):
    """The metrics-shape-drift satellite pin: sync, sharded and async
    rounds all emit exactly the registered ROUND_METRICS key set."""
    want = engine_results["schema_keys"]
    assert engine_results["sync_keys"] == want
    assert engine_results["sharded_keys"] == want
    assert engine_results["async_keys"] == want
    assert engine_results["row_keys_match_schema"] is True


def test_node_ring_compiles_out_when_gated(engine_results):
    """``with_node_ring=False`` removes the node ring from the state AND
    from the compiled program, while the scalar ring stays."""
    for tag in ("none", "disabled", "scalar_only"):
        assert engine_results[f"node_ring_is_none_{tag}"] is True
    assert engine_results["node_ring_is_none_enabled"] is False
    assert engine_results["hlo_scalar_only_differs_from_enabled"] is True
    assert engine_results["hlo_scalar_only_differs_from_off"] is True


def test_node_ring_appends_on_every_engine(engine_results):
    """One [J, NUM_NODE_COLUMNS] slab per round on the replicated, sharded
    AND async engines, stamped with the same steps as the scalar ring."""
    for tag in ("sync", "sharded"):
        assert engine_results[f"{tag}_node_rows"] == 3
        assert engine_results[f"{tag}_node_dropped"] == 0
        assert (engine_results[f"{tag}_node_steps"]
                == engine_results[f"{tag}_ring_steps"])
        assert (engine_results[f"{tag}_node_keys"]
                == engine_results["node_schema_keys"])
        assert len(engine_results[f"{tag}_node_r"][0]) == 4      # J
        # a static sync round: every node alive, every node consumed wire
        assert engine_results[f"{tag}_node_alive"] == [1.0] * 4
        assert all(v > 0 for v in engine_results[f"{tag}_node_rx"])
    assert engine_results["async_node_rows"] == 3
    assert (engine_results["async_node_keys"]
            == engine_results["node_schema_keys"])
    assert engine_results["async_node_alive"] == [1.0] * 4
    assert all(v in (0.0, 1.0)
               for v in engine_results["async_node_advance"])
    assert engine_results["async_node_ages_ok"] is True


def test_node_ring_unchanged_under_pipelining(engine_results):
    """Round-pipeline satellite pin: with ``pipeline_offsets=4`` the node
    ring's drained rows — per-node residuals, liveness, and the wire_rx
    byte accounting — are EXACTLY the sequential engine's (pipelining
    reorders the schedule, never the values or the telemetry), and the
    scalar ring matches row for row too."""
    assert engine_results["node_pipelined_rows_equal_sync"] is True
    assert engine_results["ring_pipelined_rows_equal_sync"] is True
    assert engine_results["pipelined_node_rows"] == 3
    assert all(v > 0 for v in engine_results["pipelined_node_rx"])


def test_node_residuals_sharded_equals_replicated(engine_results):
    """The acceptance pin: the sharded engine's node rows carry the
    post-psum replicated residuals — value-consistent with the replicated
    engine on the same seed/data."""
    assert engine_results["node_sync_sharded_r_close"] is True
