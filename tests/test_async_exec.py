"""Tests for the bounded-staleness async executor (repro.async_exec).

Three layers:
  * host layer — the RoundClock event model (arrival freshness, straggler
    cadence, wall-clock conventions) and aged-out straggler detection,
    no devices needed;
  * engine pins (subprocess, 8 fake devices) —
      - max_staleness=0 through the executor is bit-identical to the sync
        fused round (the ISSUE acceptance pin),
      - a staleness round with gating, revival and zero-kick absorption
        matches the jnp reference path at wire precision — params are
        stored bf16 and the int8 wire re-quantizes each round, so the pin
        is allclose(rtol=1e-2, atol=wire LSB), see the test docstring
        (fused == "dense" on a gated round, the satellite pin, for both
        the stale-gate kick and the scheduler kick),
      - ages tick / gate / revive as the arrival schedule dictates,
      - the scheduler-kick path (pending weights parked one round, absorbed
        from the next round's wire) matches the reference on a complete
        graph where round_robin really gates chords;
  * ledger layer — zero-init is never consumed, buffers hold bytes.
"""

import numpy as np
import pytest

from repro.async_exec import RoundClock, straggler_compute

from script_result import run_result


# ---------------------------------------------------------- host layer ----
def test_clock_homogeneous_fleet_everything_fresh():
    clock = RoundClock(compute_s=np.ones(4), wire_s=0.1,
                       offsets=(1, 3))
    for _ in range(5):
        arrivals, advance = clock.tick()
        assert advance.all()
        assert arrivals.all()           # every edge fresh every tick
    assert clock.rounds_done.tolist() == [5, 5, 5, 5]


def test_clock_straggler_cadence_and_staleness_alternates():
    j = 4
    clock = RoundClock(compute_s=straggler_compute(j, factor=2.0),
                       wire_s=0.1, offsets=(1, 3))
    fresh_from_straggler = []
    for t in range(8):
        arrivals, advance = clock.tick()
        # node 0 advances every other tick
        assert advance[0] == (t % 2 == 1)
        assert advance[1:].all()
        # receiver 1 reads node 0 over offset 3 ((1+3)%4 == 0)
        fresh_from_straggler.append(bool(arrivals[1][1]))
    # first read is fresh, then alternates with the 2x cadence
    assert fresh_from_straggler[0] is True
    assert sum(fresh_from_straggler) >= 3
    assert not all(fresh_from_straggler)
    assert clock.rounds_done[0] * 2 == clock.rounds_done[1]


def test_clock_wall_conventions():
    clock = RoundClock(compute_s=straggler_compute(4, factor=2.0),
                       wire_s=0.5, offsets=(1,))
    assert clock.sync_round_s == 2.5          # barrier + serialized wire
    assert clock.tick_s == 1.0                # fastest cadence
    for _ in range(3):
        clock.tick()
    assert clock.time_s == pytest.approx(3.0)


def test_first_read_always_fresh_so_zero_ledger_never_consumed():
    # even a huge wire latency only delays SENDS; the initial params count
    # as a landed send id 0, so every edge's first read is fresh
    clock = RoundClock(compute_s=np.ones(3), wire_s=50.0, offsets=(1, 2))
    arrivals, advance = clock.tick()
    assert advance.all() and arrivals.all()


def test_aged_out_nodes_reads_topology_clocks():
    from repro.core.graph import build_graph
    from repro.runtime import aged_out_nodes
    from repro.topology import TopologyConfig, TopologyRuntime

    g = build_graph("ring", 5)
    rt = TopologyRuntime(g, TopologyConfig(scheduler="stale",
                                           max_staleness=1))
    st = rt.init_state()
    age = np.zeros((5, 5), np.int32)
    age[:, 2] = 60                      # everyone's payload FROM node 2 is
    age[2, :] = 60                      # ancient, and so is its inbox
    np.fill_diagonal(age, 0)
    st = st._replace(age=np.asarray(age))
    assert aged_out_nodes(st, max_staleness=1) == [2]
    # patience: recent enough edges keep the node
    st2 = st._replace(age=np.asarray(age // 30))
    assert aged_out_nodes(st2, max_staleness=1) == []


def test_async_config_validation():
    from repro.async_exec import AsyncConfig
    with pytest.raises(ValueError):
        AsyncConfig(max_staleness=-1)
    with pytest.raises(ValueError):
        AsyncConfig(stale_gamma=-0.1)
    assert AsyncConfig().max_staleness == 1


def test_wire_ledger_shapes_and_dtypes():
    import jax.numpy as jnp
    from repro.async_exec import init_wire_ledger, wire_width
    from repro.optim import flatten

    tree = {"a": np.zeros((4, 40), np.float32),
            "b": np.zeros((4, 7), np.float32)}
    lay = flatten.FlatLayout.for_tree(tree, block_size=16)
    led = init_wire_ledger(lay, deg=2, num_nodes=4, compression="int8")
    assert led.wires.shape == (2, 4, wire_width(lay, "int8"))
    assert led.wires.dtype == jnp.int8
    led_f = init_wire_ledger(lay, deg=2, num_nodes=4, compression="none")
    assert led_f.wires.shape == (2, 4, lay.total)


# ----------------------------------------------- engine layer (8 dev) ----
_ENGINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.async_exec import AsyncConfig, AsyncExecutor
from repro.configs import get_reduced_config
from repro.core.penalty import PenaltyConfig
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import make_mesh
from repro.models import build_model
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

def make(async_cfg=None, dyn=None, fused=True, compression="none",
         topology="ring", sharded=False, penalty=None):
    return ConsensusTrainer(
        model, mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=penalty or PenaltyConfig(scheme="nap", eta0=0.1),
            topology=topology, local_steps=1, use_fused_kernel=fused,
            compression=compression,
            dyn_topology=dyn or TopologyConfig(),
            async_exec=async_cfg, shard_consensus=sharded))

def flat(st):
    return ([np.asarray(x) for x in jax.tree_util.tree_leaves(st.params)]
            + [np.asarray(st.lam), np.asarray(st.theta_bar_prev),
               np.asarray(st.penalty.eta)])

def flatu(tr, st):
    # layout-independent view (the sharded layout pads the flat total):
    # params + per-leaf lam/bar views + penalties
    return ([np.asarray(x, np.float32)
             for x in jax.tree_util.tree_leaves(st.params)]
            + [np.asarray(x) for x in jax.tree_util.tree_leaves(
                tr.layout.unpack(st.lam))]
            + [np.asarray(x) for x in jax.tree_util.tree_leaves(
                tr.layout.unpack(st.theta_bar_prev))]
            + [np.asarray(st.penalty.eta)])

def fresh_state(tr):
    st = tr.init_state(jax.random.PRNGKey(0))
    st, _ = jax.jit(tr.train_step)(st, data.batch(0))
    return st

base = make()
state0 = base.init_state(jax.random.PRNGKey(0))
state0, _ = jax.jit(base.train_step)(state0, data.batch(0))

# --- 1. max_staleness=0 through the executor == sync fused round --------
st_sync = jax.tree_util.tree_map(lambda x: x, state0)
cons = jax.jit(base.consensus_step)
st_sync, m_sync = cons(st_sync, probe)
st_sync, m_sync = cons(st_sync, probe)

tr0 = make(async_cfg=AsyncConfig(max_staleness=0))
st0 = tr0.init_state(jax.random.PRNGKey(0))
st0, _ = jax.jit(tr0.train_step)(st0, data.batch(0))
ex0 = AsyncExecutor(tr0)
st0, m0 = ex0.consensus_round(st0, probe)
st0, m0 = ex0.consensus_round(st0, probe)
out["n0_bit_identical"] = all(
    np.array_equal(a, b) for a, b in zip(flat(st_sync), flat(st0)))
out["n0_metrics_equal"] = all(
    float(m_sync[k]) == float(m0[k]) for k in m_sync)

# --- 1b. SHARDED max_staleness=0 through the executor == sharded sync ----
# (the max_staleness=0 == sync invariant re-established on the slab path)
trss = make(sharded=True)
st_ss = fresh_state(trss)
conss = jax.jit(trss.consensus_step)
st_ss, m_ss = conss(st_ss, probe)
st_ss, m_ss = conss(st_ss, probe)
tr0s = make(async_cfg=AsyncConfig(max_staleness=0), sharded=True)
st0s = fresh_state(tr0s)
ex0s = AsyncExecutor(tr0s)
st0s, m0s = ex0s.consensus_round(st0s, probe)
st0s, m0s = ex0s.consensus_round(st0s, probe)
out["n0_sharded_bit_identical"] = all(
    np.array_equal(a, b) for a, b in zip(flat(st_ss), flat(st0s)))
out["n0_sharded_metrics_equal"] = all(
    float(m_ss[k]) == float(m0s[k]) for k in m_ss)

# --- 2. staleness round: fused == reference on gating + revival ---------
# deterministic arrival schedule, N=1, int8 wire: sender 0's payloads land
# only every 3rd tick => edges reading node 0 age 0,1,2(gated -> kick),0...
# COMPLETE graph so the straggler has non-backbone chords: those are the
# edges the stale scheduler also drops from the mask (backbone never is),
# i.e. the double-absorption scenario the kick bookkeeping must dodge.
def arrivals_for(tr, tick):
    deg = len(tr.offsets)
    j = tr.num_nodes
    idx = np.arange(j)
    arr = np.zeros((deg, j), bool)
    for d, off in enumerate(tr.offsets):
        senders = (idx + off) % j
        arr[d] = (senders != 0) | (tick % 3 == 0)
    return jnp.asarray(arr)

acfg = AsyncConfig(max_staleness=1)
dyn = TopologyConfig(scheduler="stale", max_staleness=1)
for compression in ("none", "int8"):
    stats = {}
    for fused in (True, False):
        tr = make(async_cfg=acfg, dyn=dyn, fused=fused,
                  compression=compression, topology="complete")
        st = tr.init_state(jax.random.PRNGKey(0))
        st, _ = jax.jit(tr.train_step)(st, data.batch(0))
        step = jax.jit(tr.consensus_step_async)
        ms = []
        for t in range(5):
            st, m = step(st, probe, arrivals_for(tr, t), None)
            ms.append({k: float(v) for k, v in m.items()})
            if fused and compression == "none" and t == 2:
                # t=2 is the tick the straggler's edges age past the
                # bound: they were kick-absorbed IN-ROUND from the
                # ledger, so the stale scheduler mirroring them out of
                # the mask must NOT park a second (double-absorption)
                # kick for next round
                k = np.asarray(st.topo.kick)
                out["kick_double_absorb"] = float(
                    np.abs(k[:, 0]).sum() + np.abs(k[0, :]).sum())
        stats[fused] = (flat(st), ms, np.asarray(st.topo.age),
                        flatu(tr, st))
    # sharded stale round: same arrival schedule through the slab engine
    # (per-shard ledger rows, in-round kick absorption from local bytes)
    trs = make(async_cfg=acfg, dyn=dyn, fused=True,
               compression=compression, topology="complete", sharded=True)
    sts = fresh_state(trs)
    steps_ = jax.jit(trs.consensus_step_async)
    for t in range(5):
        sts, m_s = steps_(sts, probe, arrivals_for(trs, t), None)
    out[f"stale_sharded_err_{compression}"] = max(
        float(np.max(np.abs(a - b)))
        for a, b in zip(flatu(trs, sts), stats[True][3]))
    if compression == "int8":
        # per-shard ledger rows: each device's slab holds ONE shard's
        # wire width (payload slab + its own scale tail), not the row
        out["ledger_slab_widths"] = sorted(
            {int(s.data.shape[-1])
             for s in sts.ledger.wires.addressable_shards})
        out["ledger_slab_expected"] = trs.slayout.wire_width("int8")
        out["ledger_row_width"] = int(sts.ledger.wires.shape[-1])
    # "equal at wire precision": params are STORED bf16, so the two f32
    # paths legitimately differ by single bf16 ulps (rtol 1e-2 ~ 2-3
    # ulps); atol covers near-zero duals and, for int8, one LSB of the
    # absmax scale on the re-encoded wire
    atol = 2e-3 if compression == "int8" else 1e-4
    out[f"stale_close_{compression}"] = bool(all(
        np.allclose(a, b, rtol=1e-2, atol=atol)
        for a, b in zip(stats[True][0], stats[False][0])))
    out[f"stale_fused_vs_ref_err_{compression}"] = max(
        float(np.max(np.abs(a - b)))
        for a, b in zip(stats[True][0], stats[False][0]))
    out[f"stale_metric_err_{compression}"] = max(
        abs(a[k] - b[k]) / (abs(b[k]) + 1.0)
        for a, b in zip(stats[True][1], stats[False][1]) for k in a)
    if compression == "none":
        # ages of edges reading node 0 follow the 0,1,2,0,... schedule;
        # at tick 4 (last arrivals at tick 3) they sit at 1; fresh edges
        # stay at 0
        age = stats[True][2]
        out["age_into_straggler"] = int(age[1, 0])
        out["age_fresh"] = int(age[1, 2])
        # staleness gating showed up and then healed
        out["stale_seen"] = max(m["stale_edges"] for m in stats[True][1])
        out["stale_final"] = stats[True][1][-1]["stale_edges"]
        out["age_max_seen"] = max(m["age_max"] for m in stats[True][1])

# --- 3. engine scheduler-kick: fused == reference on gated rounds -------
# round_robin on COMPLETE gates the chords every epoch (on a ring the
# backbone is the whole graph and nothing can gate), so pending kicks are
# nonzero and the kernel's absorption term actually fires.
kflat = {}
for fused in (True, False):
    trk = make(dyn=TopologyConfig(scheduler="round_robin"), fused=fused,
               topology="complete")
    stk = trk.init_state(jax.random.PRNGKey(0))
    stk, _ = jax.jit(trk.train_step)(stk, data.batch(0))
    stepk = jax.jit(trk.consensus_step)
    stk, mk = stepk(stk, probe)     # parks the kick for the gated chords
    if fused:
        out["kick_pending_nonzero"] = bool(
            np.asarray(stk.topo.kick).sum() > 0)
    stk, mk = stepk(stk, probe)     # absorbs it from this round's wire
    kflat[fused] = flat(stk)
out["sched_kick_close"] = bool(all(
    np.allclose(a, b, rtol=1e-2, atol=1e-4)
    for a, b in zip(kflat[True], kflat[False])))
out["sched_kick_fused_vs_ref_err"] = max(
    float(np.max(np.abs(a - b)))
    for a, b in zip(kflat[True], kflat[False]))

# --- 4. budget-gated topology: sharded == unsharded on gated rounds -----
# force gating: a zero initial budget exhausts every edge immediately and
# a huge gate_tol drops the residual guard, so the budget scheduler gates
# all non-backbone chords of the COMPLETE graph at the end of round 1 and
# round 2 absorbs their parked kicks — the budget-gated pin of the ISSUE.
bdyn = TopologyConfig(scheduler="budget", gate_tol=1e9)
bpen = PenaltyConfig(scheme="nap", eta0=0.1, budget_init=0.0)
for compression in ("none", "int8"):
    bflat = {}
    for sharded in (True, False):
        trb = make(dyn=bdyn, compression=compression, topology="complete",
                   sharded=sharded, penalty=bpen)
        stb = fresh_state(trb)
        stepb = jax.jit(trb.consensus_step)
        stb, mb = stepb(stb, probe)     # gates chords, parks their kicks
        if sharded:
            out[f"budget_kick_pending_{compression}"] = bool(
                np.asarray(stb.topo.kick).sum() > 0)
        stb, mb = stepb(stb, probe)     # absorbs kicks from this wire
        if sharded:
            out[f"budget_gated_active_{compression}"] = float(
                mb["active_edges"])
        bflat[sharded] = flatu(trb, stb)
    out[f"budget_sharded_err_{compression}"] = max(
        float(np.max(np.abs(a - b)))
        for a, b in zip(bflat[True], bflat[False]))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def engine_results():
    return run_result(_ENGINE, timeout=1800)


def test_max_staleness_zero_bit_identical_to_sync(engine_results):
    assert engine_results["n0_bit_identical"] is True
    assert engine_results["n0_metrics_equal"] is True


def test_sharded_max_staleness_zero_bit_identical_to_sharded_sync(
        engine_results):
    """The max_staleness=0 == sync invariant re-established on the sharded
    engine (slab buffers, per-shard ledger): bit-identical incl. metrics."""
    assert engine_results["n0_sharded_bit_identical"] is True
    assert engine_results["n0_sharded_metrics_equal"] is True


def test_sharded_stale_round_matches_unsharded(engine_results):
    """Satellite pin: the sharded stale-topology round (gating, revival,
    in-round zero-kick from per-shard ledger rows) == the unsharded fused
    round — the per-element math is identical, so the bound is f32
    exactness, not just wire precision."""
    assert engine_results["stale_sharded_err_none"] <= 1e-5, engine_results
    assert engine_results["stale_sharded_err_int8"] <= 1e-5, engine_results


def test_sharded_ledger_rows_are_per_shard(engine_results):
    """Each device's ledger slab holds one shard's wire width (payload
    slab + its own int8 scale tail) — staleness absorption reads only
    local bytes."""
    assert engine_results["ledger_slab_widths"] == \
        [engine_results["ledger_slab_expected"]], engine_results
    assert engine_results["ledger_row_width"] > \
        engine_results["ledger_slab_expected"]      # guard: really sharded


def test_sharded_budget_gated_matches_unsharded(engine_results):
    """Satellite pin: budget-gated topology (scheduler gates the complete
    graph's chords, parks kicks, absorbs them next round) sharded ==
    unsharded for both compressions."""
    for comp in ("none", "int8"):
        assert engine_results[f"budget_kick_pending_{comp}"] is True
        assert engine_results[f"budget_gated_active_{comp}"] < 1.0
        assert engine_results[f"budget_sharded_err_{comp}"] <= 1e-5, \
            engine_results


def test_stale_round_fused_matches_reference(engine_results):
    """Satellite pin: fused == dense reference on rounds where staleness
    gates, revives and zero-kicks edges — at wire precision.

    Params are stored bf16, so the fused and reference f32 paths
    legitimately drift by single bf16 storage ulps per round (the f32
    difference crosses a bf16 rounding boundary); the int8 wire adds one
    LSB of the absmax scale per re-encode. The pin is therefore
    allclose(rtol=1e-2, atol=wire-LSB), not an absolute 1e-5 — which
    over a 5-tick schedule is luck, not correctness.
    """
    assert engine_results["stale_close_none"] is True, engine_results
    assert engine_results["stale_metric_err_none"] < 1e-4, engine_results
    assert engine_results["stale_close_int8"] is True, engine_results
    assert engine_results["stale_metric_err_int8"] < 1e-4, engine_results


def test_staleness_clocks_gate_and_revive(engine_results):
    assert engine_results["age_fresh"] == 0
    assert engine_results["age_into_straggler"] == 1
    assert engine_results["age_max_seen"] >= 2          # bound exceeded...
    assert engine_results["stale_seen"] > 0             # ...edges gated...
    assert engine_results["stale_final"] == 0.0         # ...and healed


def test_staleness_kick_not_double_absorbed(engine_results):
    """An edge kicked in-round when it aged out must not get a second
    scheduler kick when the stale scheduler drops it from the mask."""
    assert engine_results["kick_double_absorb"] == 0.0, engine_results


def test_engine_scheduler_kick_fused_matches_reference(engine_results):
    """The other half of the satellite pin: the SCHEDULER kick path (park
    at round t, absorb from round t+1's wire) in fused == reference, at
    the same wire precision as the staleness pin (bf16 storage ulps)."""
    assert engine_results["sched_kick_close"] is True, engine_results
    assert engine_results["kick_pending_nonzero"] is True
