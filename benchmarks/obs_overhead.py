"""Observability overhead cell: obs-off vs scalar-ring vs node-ring time.

The obs subsystem's whole pitch is "telemetry without a tax": the metrics
ring appends one [n_metrics] f32 row in-jit per round, the per-node ring
appends one [J, n_node_cols] slab next to it, and the host drains only
every K rounds. This cell measures that claim on the CPU debug mesh —
the SAME fused round timed with obs compiled out (``obs=None``), with the
scalar ring only (``with_node_ring=False``), and with the full telemetry
plane — and emits ``BENCH_obs.json`` with two gated scalars
(``check_regression.py``, additive tolerance over committed baselines):
``obs_overhead_ratio`` (full obs vs off, <= 3 points) and
``node_ring_overhead_ratio`` (node ring vs scalar-ring baseline,
<= 3 points — the per-node plane must stay in the noise too).

Measurement discipline: CPU interpret-mode rounds are slow (~100 ms) and
noisy, so the three variants are timed ALTERNATELY round by round (drift
in machine load hits all medians equally), the within-round order rotates
every round (whoever runs later inherits the others' cache pressure —
fixing the order has been observed to bias the ratio by >10 points), and
the per-variant cost is the mean of the LOWEST-QUARTILE round times.
Scheduler interference on a shared runner only ever ADDS time (spikes of
+10 ms on a ~25 ms round are routine), so medians of the variants
inherit independent noise that dwarfs a sub-millisecond ring append; the
low-quartile floor is what the compiled program actually costs. The
host-side drain is timed separately and amortized over its cadence
(``drain_ms / drain_every``) INTO the obs-on cost, so the gate still
covers the full telemetry path, and the cell finishes by writing a
real ObsWriter artifact set under ``results/obs_bench/`` and validating it
(the same well-formedness gate CI runs on launcher drills).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import require_devices, write_json

RING_CAP = 64
DRAIN_EVERY = 8
ROUNDS = 96     # quartile floor needs ~24 clean samples per variant; at 32
                # rounds one loaded stretch still swung the ratio 0-4%


def run(rounds: int = ROUNDS) -> dict:
    import jax
    require_devices("obs_overhead", 8)
    from repro.configs import get_reduced_config
    from repro.core.penalty import PenaltyConfig
    from repro.data import DataConfig, SyntheticTokens
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.obs import ObsConfig, ObsWriter, validate_obs_dir
    from repro.obs import schema as obs_schema
    from repro.optim import ConsensusConfig, ConsensusTrainer
    from repro.optim.adamw import AdamWConfig

    mesh = make_debug_mesh(multi_pod=True)
    cfg = get_reduced_config("qwen3-4b")
    model = build_model(cfg)
    data = SyntheticTokens(DataConfig(
        vocab=cfg.vocab, seq_len=32, batch_per_node=2, num_nodes=2))

    def make(obs):
        return ConsensusTrainer(
            model, mesh, adamw=AdamWConfig(lr=1e-2),
            consensus=ConsensusConfig(
                penalty=PenaltyConfig(scheme="nap", eta0=0.1),
                topology="ring", local_steps=4, obs=obs))

    tr_off = make(None)
    tr_scalar = make(ObsConfig(ring_capacity=RING_CAP,
                               drain_every=DRAIN_EVERY,
                               with_node_ring=False))
    tr_on = make(ObsConfig(ring_capacity=RING_CAP, drain_every=DRAIN_EVERY))
    st_off = tr_off.init_state(jax.random.PRNGKey(0))
    st_scalar = tr_scalar.init_state(jax.random.PRNGKey(0))
    st_on = tr_on.init_state(jax.random.PRNGKey(0))
    train_off, cons_off = tr_off.jit_step_fns()
    train_scalar, cons_scalar = tr_scalar.jit_step_fns()
    train_on, cons_on = tr_on.jit_step_fns()
    st_off, m = train_off(st_off, data.batch(0))
    jax.block_until_ready(m["loss"])
    st_scalar, m = train_scalar(st_scalar, data.batch(0))
    jax.block_until_ready(m["loss"])
    st_on, m = train_on(st_on, data.batch(0))
    jax.block_until_ready(m["loss"])
    # warm/compile all three rounds before any timing
    st_off, cm = cons_off(st_off, data.batch(0, probe=True))
    jax.block_until_ready(cm["r_max"])
    st_scalar, cm = cons_scalar(st_scalar, data.batch(0, probe=True))
    jax.block_until_ready(cm["r_max"])
    st_on, cm = cons_on(st_on, data.batch(0, probe=True))
    jax.block_until_ready(cm["r_max"])

    import os
    obs_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results", "obs_bench")
    writer = ObsWriter(obs_dir, meta={
        "arch": "qwen3-4b (reduced)", "wire_codec": tr_on.codec_name,
        "wire_bytes_per_round":
            tr_on.codec.wire_bytes() * max(len(tr_on.offsets), 1),
        "offsets": [int(o) for o in tr_on.offsets]})
    writer.drain(st_on, step=0)     # flush the warm-up round's ring row
    t_off, t_scalar, t_on, t_drain = [], [], [], []
    n_rows = 0
    for s in range(1, rounds + 1):
        probe = data.batch(s, probe=True)

        def round_off():
            nonlocal st_off
            t0 = time.time()
            st_off, cm = cons_off(st_off, probe)
            jax.block_until_ready(cm["r_max"])
            t_off.append(time.time() - t0)

        def round_scalar():
            nonlocal st_scalar
            t0 = time.time()
            st_scalar, cm = cons_scalar(st_scalar, probe)
            jax.block_until_ready(cm["r_max"])
            t_scalar.append(time.time() - t0)

        def round_on():
            nonlocal st_on, n_rows
            t0 = time.time()
            st_on, cm = cons_on(st_on, probe)
            jax.block_until_ready(cm["r_max"])
            t_on.append(time.time() - t0)
            if s % DRAIN_EVERY == 0:    # timed apart, amortized back in
                t0 = time.time()
                n_rows += writer.drain(st_on, step=s)
                t_drain.append(time.time() - t0)

        # rotate within-round order so no variant always runs cold/hot
        trio = [round_off, round_scalar, round_on]
        for i in range(3):
            trio[(s + i) % 3]()
    n_rows += writer.drain(st_on, step=rounds)      # tail rows
    def low_quartile_mean(ts):
        k = max(1, len(ts) // 4)
        return float(np.mean(np.sort(np.asarray(ts))[:k]))

    low_off = low_quartile_mean(t_off)
    low_scalar = low_quartile_mean(t_scalar)
    low_on = low_quartile_mean(t_on)
    drain_ms = float(np.median(t_drain)) * 1e3 if t_drain else 0.0
    drain_amortized = drain_ms * 1e-3 / DRAIN_EVERY
    # clamped at 0: on a noisy 2-core runner the obs-on floor routinely
    # lands UNDER obs-off; negative "overhead" is noise, not a speedup
    overhead = max(0.0, (low_on + drain_amortized) / max(low_off, 1e-9)
                   - 1.0)
    # the node ring's own marginal cost: full plane vs scalar-ring-only
    # (both pay the append discipline, only one carries the [J, cols] slab)
    node_ring_overhead = max(0.0, low_on / max(low_scalar, 1e-9) - 1.0)
    rollup = writer.finalize()
    report = validate_obs_dir(obs_dir)
    assert report["ok"], f"obs artifact set malformed: {report['errors']}"
    assert n_rows == rounds, (n_rows, rounds)
    assert rollup["dropped_rows"] == 0

    j = tr_on.num_nodes
    bench = {
        "mesh": f"2x2x2 ({len(jax.devices())} {jax.devices()[0].platform} "
                f"devices, {jax.devices()[0].device_kind})",
        "arch": "qwen3-4b (reduced)",
        "rounds": {
            "obs_off": {"round_ms": round(low_off * 1e3, 2)},
            "obs_scalar": {"round_ms": round(low_scalar * 1e3, 2)},
            "obs_on": {"round_ms": round(low_on * 1e3, 2)},
        },
        "obs_overhead_ratio": round(overhead, 4),
        "node_ring_overhead_ratio": round(node_ring_overhead, 4),
        "estimator": f"lowest-quartile mean of {rounds} alternating rounds"
                     " + amortized drain",
        "ring": {"capacity": RING_CAP, "drain_every": DRAIN_EVERY,
                 "columns": obs_schema.NUM_COLUMNS,
                 "ring_hbm_bytes": 4 * RING_CAP * obs_schema.NUM_COLUMNS},
        "node_ring": {"capacity": RING_CAP, "num_nodes": j,
                      "columns": obs_schema.NUM_NODE_COLUMNS,
                      "ring_hbm_bytes":
                          4 * RING_CAP * j * obs_schema.NUM_NODE_COLUMNS},
        "drain": {"rows_drained": n_rows,
                  "drain_ms": round(drain_ms, 3),
                  "dropped": rollup["dropped_rows"],
                  "dropped_node_rows":
                      rollup["per_node"].get("dropped_rows", 0)},
    }
    path = write_json("BENCH_obs.json", bench)
    print(f"obs bench: off {low_off*1e3:.1f}ms scalar {low_scalar*1e3:.1f}ms "
          f"on {low_on*1e3:.1f}ms drain {drain_ms:.2f}ms/{DRAIN_EVERY}r "
          f"overhead {overhead*100:.1f}% node-ring "
          f"{node_ring_overhead*100:.1f}% ({n_rows} rows drained)")
    print(f"wrote {path}")
    return bench


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    args = ap.parse_args()
    run(rounds=args.rounds)
