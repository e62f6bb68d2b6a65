"""Tests for the dynamic-topology runtime (repro.topology).

Three layers:
  * property tests — every scheduler's masked graph stays connected every
    epoch (incl. across node churn), epochs/liveness invariants;
  * dense-path behavior — budget-gated NAP matches fixed-topology NAP on
    the paper's J=12 synthetic least-squares problem (iterations-to-
    converge under the paper's §5 relative-objective criterion) for ring
    and cluster, then sheds edges post-convergence without hurting error;
  * engine pins (subprocess, 8 fake devices) — scheduler="static" is
    bit-identical to the PR 1 fused round, and a mid-run node drop on the
    debug mesh completes training without recompiling the fused step.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (ConsensusADMM, PenaltyConfig, build_graph,
                        connected_components, init_penalty_state)
from repro.topology import (SCHEDULERS, TopologyConfig, TopologyRuntime,
                            spanning_backbone)

from proptest import sweep, draw_topology

from script_result import run_result


def _alive_components(mask, alive):
    m = np.asarray(mask) & alive[:, None] & alive[None, :]
    return [c for c in connected_components(m) if alive[c[0]]]


# ------------------------------------------------------- property layer ----
def test_backbone_spans_every_topology():
    def prop(rng, i):
        j = int(rng.integers(2, 16))
        g = build_graph(draw_topology(rng, j), j)
        bb = spanning_backbone(g)
        assert not np.any(bb & ~g.adj), "backbone must be a subgraph"
        assert len(connected_components(bb)) == 1
    sweep(prop, cases=20, seed=11)


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_scheduler_masks_stay_connected_every_epoch(scheduler):
    """The headline invariant: mask ⊇ backbone ⇒ connected, symmetric,
    diagonal-free — for every scheduler, topology, and epoch."""
    def prop(rng, i):
        j = int(rng.integers(3, 12))
        g = build_graph(draw_topology(rng, j), j)
        rt = TopologyRuntime(g, TopologyConfig(
            scheduler=scheduler, churn=True, seed=i,
            activation_p=float(rng.uniform(0.1, 0.9))))
        st = rt.init_state()
        pen = init_penalty_state(PenaltyConfig(scheme="nap"), j)
        # drive the budget gate hard: pretend everything is exhausted+close
        pen = pen._replace(cum_tau=pen.budget + 1.0)
        for t in range(6):
            st = rt.update(st, penalty=pen, r_norm=jnp.zeros(j))
            m = np.asarray(st.mask)
            assert np.array_equal(m, m.T), (scheduler, t)
            assert not m.diagonal().any(), (scheduler, t)
            assert not np.any(m & ~(np.asarray(st.backbone)
                                    | np.asarray(st.repair) | g.adj))
            comps = _alive_components(m, np.ones(j, bool))
            assert len(comps) == 1, (scheduler, t, comps)
    sweep(prop, cases=10, seed=13)


@pytest.mark.parametrize("scheduler", SCHEDULERS)
def test_masks_stay_connected_across_churn(scheduler):
    """Dense-universe churn (repair may use any pair): drop nodes down to
    two survivors; the masked graph must stay connected at every epoch."""
    def prop(rng, i):
        j = int(rng.integers(4, 12))
        g = build_graph(draw_topology(rng, j), j)
        rt = TopologyRuntime(g, TopologyConfig(scheduler=scheduler,
                                               churn=True, seed=i),
                             edge_universe=~np.eye(j, dtype=bool))
        st = rt.init_state()
        pen = init_penalty_state(PenaltyConfig(scheme="nap"), j)
        alive = np.ones(j, bool)
        victims = rng.permutation(j)[: j - 2]
        for v in victims:
            st = rt.drop_node(st, int(v))
            alive[int(v)] = False
            st = rt.update(st, penalty=pen, r_norm=jnp.zeros(j))
            m = np.asarray(st.mask)
            assert not m[int(v)].any() and not m[:, int(v)].any()
            assert np.array_equal(np.asarray(st.node_alive), alive)
            comps = _alive_components(m, alive)
            assert len(comps) == 1, (scheduler, int(v), comps)
    sweep(prop, cases=8, seed=17)


def test_single_drop_repairable_within_engine_offset_superset():
    """Engine-universe churn: one node loss must always be repairable
    through the compiled circulant offset superset."""
    def prop(rng, i):
        j = int(rng.integers(4, 14))
        g = build_graph(draw_topology(rng, j), j)
        rt = TopologyRuntime(g, TopologyConfig(scheduler="static",
                                               churn=True))
        st = rt.drop_node(rt.init_state(), int(rng.integers(0, j)))
        alive = np.asarray(st.node_alive)
        comps = _alive_components(np.asarray(st.mask), alive)
        assert len(comps) == 1, comps
    sweep(prop, cases=20, seed=19)


def test_budget_gate_latches_and_revives_on_topup():
    j = 6
    g = build_graph("complete", j)
    rt = TopologyRuntime(g, TopologyConfig(scheduler="budget",
                                           gate_tol=1e-2))
    st = rt.init_state()
    pen = init_penalty_state(PenaltyConfig(scheme="nap"), j)
    # exhaust every budget, residuals below tolerance -> non-backbone gated
    pen_exh = pen._replace(cum_tau=pen.budget + 1.0)
    st = rt.update(st, penalty=pen_exh, r_norm=jnp.zeros(j))
    gated = np.asarray(~st.mask & g.adj)
    assert gated.any(), "nothing gated"
    # residuals drift back up: the latch must hold while exhausted
    st2 = rt.update(st, penalty=pen_exh, r_norm=jnp.full(j, 1e3))
    assert np.array_equal(np.asarray(st.mask), np.asarray(st2.mask))
    # top-up (budget above cum_tau) revives everything
    pen_rev = pen_exh._replace(budget=pen_exh.cum_tau + 1.0)
    st3 = rt.update(st2, penalty=pen_rev, r_norm=jnp.full(j, 1e3))
    assert np.array_equal(np.asarray(st3.mask), g.adj)
    # epochs counted each flip
    assert np.asarray(st3.epoch)[gated].min() >= 2


def test_stale_scheduler_gates_on_age_and_revives_on_arrival():
    """The async executor's scheduler: edges deactivate while either
    direction's payload age exceeds the bound and revive (no latch) the
    epoch a fresh payload resets the clock."""
    from repro.topology import tick_age
    j = 6
    g = build_graph("complete", j)
    rt = TopologyRuntime(g, TopologyConfig(scheduler="stale",
                                           max_staleness=1))
    st = rt.init_state()
    pen = init_penalty_state(PenaltyConfig(scheme="nap"), j)
    # ages zero -> degenerates to static
    st = rt.update(st, penalty=pen, r_norm=jnp.zeros(j))
    assert np.array_equal(np.asarray(st.mask), g.adj)
    # node 0's payloads stop arriving: after 2 stale ticks its non-backbone
    # edges gate (one direction aging is enough — sym_age is the max)
    fresh = np.ones((j, j), bool)
    fresh[:, 0] = False
    for _ in range(2):
        st = tick_age(st, jnp.asarray(fresh))
    st = rt.update(st, penalty=pen, r_norm=jnp.zeros(j))
    m = np.asarray(st.mask)
    bb = np.asarray(st.backbone)
    assert not m[0, 2:-1].any()                   # chords to node 0 gated
    assert np.array_equal(m, m.T)
    assert (m & ~bb)[1:, 1:].any()                # other edges untouched
    assert np.array_equal(m | bb, m)              # backbone subset of mask
    # a fresh arrival resets the clocks -> full revival, no latch
    st = tick_age(st, jnp.asarray(np.ones((j, j), bool)))
    st = rt.update(st, penalty=pen, r_norm=jnp.zeros(j))
    assert np.array_equal(np.asarray(st.mask), g.adj)


def test_round_robin_rotates_and_random_is_deterministic():
    j = 8
    g = build_graph("complete", j)
    pen = init_penalty_state(PenaltyConfig(scheme="nap"), j)
    rt = TopologyRuntime(g, TopologyConfig(scheduler="round_robin"))
    st = rt.init_state()
    masks = []
    for _ in range(3):
        st = rt.update(st, penalty=pen, r_norm=jnp.zeros(j))
        masks.append(np.asarray(st.mask))
    assert not np.array_equal(masks[0], masks[1])  # rotation moved
    rt2 = TopologyRuntime(g, TopologyConfig(scheduler="random", seed=3))
    a = rt2.update(rt2.init_state(), penalty=pen, r_norm=jnp.zeros(j))
    b = rt2.update(rt2.init_state(), penalty=pen, r_norm=jnp.zeros(j))
    assert np.array_equal(np.asarray(a.mask), np.asarray(b.mask))


def test_drop_node_star_cut_vertex_chains_all_components():
    """Satellite bugfix pin: dropping the hub of a star-like cut region
    must reconnect ALL resulting components (>2 of them)."""
    from repro.core import Graph, drop_node
    j = 7
    adj = np.zeros((j, j), bool)
    for leaf in range(1, j):            # star: 0 is a cut vertex of 6 leaves
        adj[0, leaf] = adj[leaf, 0] = True
    g = Graph(j, adj, "star")
    g2 = drop_node(g, 0)
    assert g2.num_nodes == j - 1
    assert g2.is_connected()
    # spanning chain over components: exactly components-1 = 5 bridges
    assert g2.num_edges == j - 2


def test_expected_active_fraction_bounds():
    g = build_graph("complete", 10)
    for sched in SCHEDULERS:
        rt = TopologyRuntime(g, TopologyConfig(scheduler=sched))
        f = rt.expected_active_fraction()
        assert 0.0 < f <= 1.0, (sched, f)
    assert TopologyRuntime(
        g, TopologyConfig()).expected_active_fraction() == 1.0


# ----------------------------------------------------- dense-path layer ----
def _lsq_problem(j, d=4, n=16, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(j, n, d)).astype(np.float32)
    w_true = rng.normal(size=d).astype(np.float32)
    b = A @ w_true + 0.01 * rng.normal(size=(j, n)).astype(np.float32)
    w_star = np.linalg.lstsq(A.reshape(-1, d), b.reshape(-1), rcond=None)[0]
    theta0 = {"w": jnp.asarray(rng.normal(size=(j, d)).astype(np.float32))}
    return (jnp.asarray(A), jnp.asarray(b)), theta0, w_star


def _lsq_obj(data, th):
    Ai, bi = data
    return jnp.sum((Ai @ th["w"] - bi) ** 2)


@pytest.mark.parametrize("topo", ["ring", "cluster"])
def test_budget_matches_fixed_topology_nap_iterations(topo):
    """Acceptance pin: budget-gated NAP converges in <= the iterations of
    fixed-topology NAP on the J=12 synthetic problem (paper §5 criterion),
    with the SAME trajectory while no edge is gated."""
    j = 12
    data, theta0, w_star = _lsq_problem(j, seed=3)
    iters = {}
    for label, tcfg in (("fixed", None),
                        ("budget", TopologyConfig(scheduler="budget"))):
        eng = ConsensusADMM(objective=_lsq_obj,
                            penalty_cfg=PenaltyConfig(scheme="nap", eta0=1.0),
                            graph=build_graph(topo, j),
                            inner_steps=30, inner_lr=1.0, topology_cfg=tcfg)
        st = eng.init(theta0)
        st, hist = eng.run(st, data, max_iters=400, rel_tol=1e-3)
        iters[label] = hist["iterations"]
        err = np.abs(np.asarray(st.theta["w"]) - w_star).max()
        assert err < 0.05, (topo, label, err)
    assert iters["budget"] <= iters["fixed"], iters


def test_budget_sheds_edges_post_convergence_without_drift():
    """§4 realized: once locally converged, exhausted edges detach — wire
    drops while the iterate stays at the consensus solution."""
    j = 12
    data, theta0, w_star = _lsq_problem(j, seed=3)
    eng = ConsensusADMM(objective=_lsq_obj,
                        penalty_cfg=PenaltyConfig(scheme="nap", eta0=1.0),
                        graph=build_graph("complete", j),
                        inner_steps=30, inner_lr=1.0,
                        topology_cfg=TopologyConfig(scheduler="budget"))
    st = eng.init(theta0)
    st, _ = eng.run(st, data, max_iters=400, rel_tol=1e-3)
    for _ in range(100):
        st, m = eng.step(st, data)
    active = float(np.asarray(st.topo.mask).sum()
                   / max(build_graph("complete", j).adj.sum(), 1))
    assert active < 0.5, active                 # most edges shed
    err = np.abs(np.asarray(st.theta["w"]) - w_star).max()
    assert err < 0.01, err                      # iterate stayed put
    comps = connected_components(np.asarray(st.topo.mask))
    assert len(comps) == 1                      # backbone held


def test_dense_node_drop_mid_run_recovers():
    j = 8
    data, theta0, w_star = _lsq_problem(j, seed=5)
    eng = ConsensusADMM(objective=_lsq_obj,
                        penalty_cfg=PenaltyConfig(scheme="nap", eta0=1.0),
                        graph=build_graph("ring", j),
                        inner_steps=30, inner_lr=1.0,
                        topology_cfg=TopologyConfig(scheduler="static",
                                                    churn=True))
    st = eng.init(theta0)
    for _ in range(10):
        st, _ = eng.step(st, data)
    st = eng.apply_churn(st, 3)
    for _ in range(150):
        st, m = eng.step(st, data)
    alive = np.asarray(st.topo.node_alive)
    w = np.asarray(st.theta["w"])[alive]
    # survivors reach consensus among themselves (node 3's data is gone,
    # so the solution is the SURVIVORS' least-squares, not w_star)
    assert np.abs(w - w.mean(axis=0)).max() < 0.05


# ------------------------------------------------ engine layer (8 dev) ----
_ENGINE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
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

def make(dyn, fused=True, topology="ring"):
    return ConsensusTrainer(
        model, mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=PenaltyConfig(scheme="nap", eta0=0.1),
            topology=topology, local_steps=1, use_fused_kernel=fused,
            dyn_topology=dyn))

base = make(TopologyConfig())                  # PR 1 path (static, no churn)
state0 = base.init_state(jax.random.PRNGKey(0))
state0, _ = jax.jit(base.train_step)(state0, data.batch(0))
probe = data.batch(0, probe=True)

def run2(tr, st):
    cons = jax.jit(tr.consensus_step)
    st = jax.tree_util.tree_map(lambda x: x, st)
    st, _ = cons(st, probe)
    st, m = cons(st, probe)
    return st, m

def flat(st):
    return ([np.asarray(x) for x in jax.tree_util.tree_leaves(st.params)]
            + [np.asarray(st.lam), np.asarray(st.theta_bar_prev),
               np.asarray(st.penalty.eta)])

# --- static == PR 1 fused round, bit for bit ----------------------------
# On complete the churn offset superset EQUALS the graph offsets, so the
# two programs stack identical wires and the all-ones traced mask must
# reproduce the ungated kernel exactly. (A ring superset adds offsets,
# which legitimately re-pairs fma rounding — covered by the 1e-5 dynamic
# check below instead.)
base_c = make(TopologyConfig(), topology="complete")
st0c = base_c.init_state(jax.random.PRNGKey(0))
st0c, _ = jax.jit(base_c.train_step)(st0c, data.batch(0))
st_a, _ = run2(base_c, st0c)
st_b, _ = run2(make(TopologyConfig(scheduler="static", churn=True),
                    topology="complete"), st0c)
out["static_bit_identical"] = all(
    np.array_equal(a, b) for a, b in zip(flat(st_a), flat(st_b)))

# --- mid-run node drop: no recompilation of the fused step --------------
tr = make(TopologyConfig(scheduler="budget", churn=True))
st = tr.init_state(jax.random.PRNGKey(1))
train = jax.jit(tr.train_step)
cons = jax.jit(tr.consensus_step)
for step in range(4):
    st, _ = train(st, data.batch(step))
    st, m = cons(st, probe)
pre = (train._cache_size(), cons._cache_size())
st = tr.apply_churn(st, 2)
for step in range(4, 8):
    st, _ = train(st, data.batch(step))
    st, m = cons(st, probe)
out["cache_grew"] = [train._cache_size() - pre[0],
                     cons._cache_size() - pre[1]]
out["r_max_after_drop"] = float(m["r_max"])
out["active_after_drop"] = float(m["active_edges"])
out["alive"] = np.asarray(st.topo.node_alive).tolist()

# --- dynamic fused == dynamic unfused reference -------------------------
tru = make(TopologyConfig(scheduler="round_robin", churn=True), fused=False)
trf = make(TopologyConfig(scheduler="round_robin", churn=True), fused=True)
stf, mf = run2(trf, state0)
stu, mu = run2(tru, state0)
out["dyn_fused_vs_ref_err"] = max(
    float(np.max(np.abs(a - b))) for a, b in zip(flat(stf), flat(stu)))
out["dyn_metric_err"] = max(
    abs(float(mf[k]) - float(mu[k])) / (abs(float(mu[k])) + 1.0)
    for k in mf)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def engine_results():
    return run_result(_ENGINE, timeout=1800)


def test_static_scheduler_bit_identical_to_fused_round(engine_results):
    assert engine_results["static_bit_identical"] is True


def test_node_drop_without_recompile(engine_results):
    assert engine_results["cache_grew"] == [0, 0], engine_results
    assert engine_results["alive"] == [True, True, False, True]
    assert np.isfinite(engine_results["r_max_after_drop"])
    assert 0.0 < engine_results["active_after_drop"] < 1.0


def test_dynamic_fused_matches_reference(engine_results):
    assert engine_results["dyn_fused_vs_ref_err"] < 1e-5, engine_results
    assert engine_results["dyn_metric_err"] < 1e-5, engine_results
